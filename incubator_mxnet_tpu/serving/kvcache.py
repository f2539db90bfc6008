"""Paged KV-cache block pool with prefix sharing, and the cache's form
(:func:`cache_forms`: how what a layer caches is stored, written, read).

The :class:`BlockPool` is the host-side allocator behind the paged
``GenerationEngine``: device KV storage is carved into fixed-size blocks
of ``block_size`` token positions, and each live slot holds an ordered
*block table* (a list of block ids) instead of a dense ``max_len`` strip.
Three properties fall out:

* **Fragmentation-free packing** — a request reserves only
  ``ceil((prompt + budget) / block_size)`` blocks, so short streams no
  longer pay for ``max_len`` worth of cache and many more of them fit in
  the same byte budget.
* **Prefix sharing** — every *full* block of a prompt is keyed by a
  chained blake2b digest (digest of the previous block's digest plus
  this block's tokens), so two requests with a common prefix map their
  leading blocks to the same physical storage. A digest match implies
  token-exact prefix equality — keys are 128-bit content digests, not
  Python ``hash()`` values, so distinct prompts cannot alias. Shared
  blocks are refcounted; the joiner skips prefill for the shared span
  entirely.
* **Copy-on-write** — a writer that needs to mutate a block with
  refcount > 1 asks :meth:`copy_on_write` for a private copy first. The
  serving flow never mutates shared blocks by construction (only *full*,
  immutable prompt blocks are ever registered for sharing), but the COW
  primitive is part of the pool contract and unit-tested so future
  writers (e.g. speculative-decode rollback) inherit it.

Block id 0 is the reserved **null block**: block tables are padded with
it and out-of-range scatter positions are redirected to it, so garbage
writes from padded prefill rows land in a sink nobody ever attends to.

**Burst write contract** (``GenerationEngine.decode_burst``): the
scanned multi-token decode advances a slot at most ``budget`` positions
past its current length, and every admit reserves
``blocks_for(prompt + budget)`` up front — so the burst's furthest KV
write (position ``prompt + budget - 1`` at the worst case) always lands
inside the slot's reserved table and **no extra headroom is needed for
any scan_steps**. Slots that finish mid-burst have their remaining
in-scan writes redirected to the null block, the same sink padded
prefill rows use.

Eviction: a cached block whose refcount drops to 0 is *not* returned to
the free list — it stays in the prefix cache, instantly reusable by the
next request with the same prefix, and is only reclaimed (LRU) when the
free list runs dry. ``mxtpu_prefix_cache_evictions`` counts reclaims.

All methods take an internal lock; the pool is shared between the
batcher worker thread and HTTP admission checks.
"""
from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict, deque
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from ..base import MXNetError
from . import metrics as _m

__all__ = ["BlockPool", "KVLayout", "SnapshotPlan", "NO_SNAPSHOTS",
           "blocks_for", "NULL_BLOCK", "cache_forms", "grouped_pool_shape",
           "pool_layout", "GroupedKV", "StatedRows", "StateLeaves"]

NULL_BLOCK = 0


class KVLayout(NamedTuple):
    """What a served model's layers keep per cached position, as the model
    states it (``block.kv_layout()``): the pools are allocated from this
    and the pool's bytes counted from it, never from a model's insides.
    ``windows`` has one entry a layer: None where a layer reads every
    earlier position, else the number of latest positions it reads (a
    mask and a lower bound on the attention's work; no block is freed
    behind a window yet, ROADMAP M3).  ``states`` has one entry a layer
    too: None where the layer keeps keys and values in the pool, else the
    leaves ``((shape, dtype), ...)`` of the state of constant size that the
    layer keeps a sequence INSTEAD (a recurrent layer: it has no blocks);
    a model that does not state it has none.  ``rows`` says what a cached
    position keeps in a layer that is not grouped-query: None where the
    layer keeps K and V of ``kv_heads x head_dim`` each (two pools,
    :meth:`pool_shape`), else the rows ``((features, dtype), ...)`` it
    keeps INSTEAD, one pool a row (:meth:`row_pool_shape`) — a latent
    layer one row that is key and value both, an indexed latent layer that
    row and its index key.  ``selects`` names, for a layer whose attention
    runs over a chosen set of its cached positions, how many it chooses
    (None: it reads them all, or its window)."""
    num_layers: int
    kv_heads: int
    head_dim: int
    dtype: str
    windows: Tuple[Optional[int], ...]
    max_length: int
    states: Tuple[Optional[tuple], ...] = ()
    rows: Tuple[Optional[tuple], ...] = ()
    selects: Tuple[Optional[int], ...] = ()

    @classmethod
    def of(cls, stated: dict) -> "KVLayout":
        try:
            n = int(stated["num_layers"])
            lay = cls(n, int(stated["kv_heads"]),
                      int(stated["head_dim"]), str(stated["dtype"]),
                      tuple(None if w is None else int(w)
                            for w in stated["windows"]),
                      int(stated["max_length"]),
                      tuple(None if leaves is None else tuple(
                          (tuple(int(d) for d in shape), str(dtype))
                          for shape, dtype in leaves)
                          for leaves in stated.get("states") or (None,) * n),
                      tuple(None if kept is None else tuple(
                          (int(features), str(dtype))
                          for features, dtype in kept)
                          for kept in stated.get("rows") or (None,) * n),
                      tuple(None if k is None else int(k)
                            for k in stated.get("selects") or (None,) * n))
        except (KeyError, TypeError, ValueError) as e:
            raise MXNetError(f"kv_layout() must give {cls._fields}: {e!r}")
        if not len(lay.windows) == len(lay.states) == len(lay.rows) \
                == len(lay.selects) == lay.num_layers:
            raise MXNetError(
                f"kv_layout(): {len(lay.windows)} windows, "
                f"{len(lay.states)} states, {len(lay.rows)} rows and "
                f"{len(lay.selects)} selects for {lay.num_layers} layers")
        return lay

    @property
    def kv_layers(self) -> Tuple[int, ...]:
        """The layers that keep blocks: a K and a V pool each, or the
        pools of the rows they state."""
        return tuple(l for l, s in enumerate(self.states) if s is None)

    def layer_rows(self, l: int) -> tuple:
        """What a cached position keeps in layer ``l``, as ``((features,
        dtype), ...)``: K and V of a grouped-query layer, the stated rows
        of another, nothing of a layer that keeps a state instead."""
        if self.states[l] is not None:
            return ()
        if self.rows[l] is not None:
            return self.rows[l]
        return ((self.kv_heads * self.head_dim, self.dtype),) * 2

    #: a TPU's vector registers and the tiles its memory is laid in are this
    #: many features wide
    LANES = 128

    def pool_shape(self, num_blocks: int, block_size: int, device):
        """``(shape, position_major)``: the shape each K and V pool of this
        model is stored in on ``device``, read off platform, shape and type
        and nothing else.

        A pool is stated as ``[N, H, bs, D]`` (block, head, position,
        feature) and the paged programs keep it block, position, head,
        feature: a position's ``(H, D)`` row is one piece — what a step's
        row write sets — and a kernel page is ``[bs, H, D]``.  Every
        program takes the pools and returns them, and an array crosses a
        program's boundary in the layout its device gives any array of that
        shape, so a pool whose default layout is another order is copied
        whole on the way in and again on the way out.  On a TPU the
        default of ``f32[N, 16, 16, 64]`` puts N on the lanes (features
        under 128 would pad them), which no program keeps.  So where the
        stated shape does not rest row-major the pool is stored
        position-major, ``[N, bs, H, Dp]`` with ``Dp`` the features
        rounded up to whole :attr:`LANES` (the lanes past ``D`` hold
        zeros: the bytes the stated shape takes in the programs' layout
        anyway), provided THAT rests row-major; then its default layout
        is the programs' and nothing is copied.  Everywhere else — off
        the TPU, one KV head of 128 features (``bf16[N, 1, 16, 128]``
        rests row-major as stated), a head count the device would rather
        not put on the sublanes — the stated shape stays, and the
        programs are the ones they were.  (A pinned
        ``jax.experimental.layout.Format`` would say the same, but an
        executable read back from the persistent compile cache returns its
        results in default layouts — jax 0.9.0 with this libtpu — so only
        a default layout survives a warm start: docs/serving.md.)"""
        import jax.numpy as jnp
        stated = (int(num_blocks), self.kv_heads, int(block_size),
                  self.head_dim)
        if device.platform != "tpu":
            return stated, False

        def rests_row_major(shape):
            from jax.experimental.layout import Layout
            order = Layout.from_pjrt_layout(device.client.get_default_layout(
                jnp.dtype(self.dtype), shape, device)).major_to_minor
            kept = [a for a in order if shape[a] != 1]
            return kept == sorted(kept)

        if rests_row_major(stated):
            return stated, False
        by_position = (stated[0], stated[2], stated[1],
                       -(-self.head_dim // self.LANES) * self.LANES)
        if rests_row_major(by_position):
            return by_position, True
        return stated, False

    def row_pool_shape(self, num_blocks: int, block_size: int,
                       features: int, device):
        """The shape the pool of one stated row (``rows``) is stored in on
        ``device``: ``[N, bs, F]`` — a block's positions, each one row —
        with the features rounded up to whole :attr:`LANES` on a TPU,
        where the tiles memory is laid in take those bytes anyway (the
        lanes past ``F`` hold zeros) and a kernel's page is whole tiles."""
        if device.platform == "tpu":
            features = -(-int(features) // self.LANES) * self.LANES
        return int(num_blocks), int(block_size), int(features)

    def block_bytes(self, block_size: int) -> int:
        """Bytes behind one block of ``block_size`` positions: what every
        layer keeps a position (:meth:`layer_rows`), as stated."""
        import jax.numpy as jnp
        return int(block_size) * sum(
            features * jnp.dtype(dtype).itemsize
            for l in range(self.num_layers)
            for features, dtype in self.layer_rows(l))


def blocks_for(tokens: int, block_size: int) -> int:
    """Blocks needed to hold ``tokens`` positions."""
    return max(0, -(-int(tokens) // int(block_size)))


# -- the cache's form (docs/serving.md "The cache's form") --------------------
# What kind of thing a layer caches and how the pool that holds it is stored
# is decided HERE; the kernels, which read a pool in place, are the format's
# only other holder.  The engine keeps one form a layer and asks it.  A form
# holds plain values; whether the K/V pools are stored position-major it is
# TOLD at every call (a caller that traces for another device assigns
# ``GenerationEngine._position_major``).

def _to_lanes(rows, pool):
    """``rows`` (..., D) as a pool whose last axis is whole lanes holds
    them: zeros on the lanes past D."""
    from jax import lax
    pad = pool.shape[-1] - rows.shape[-1]
    if not pad:
        return rows
    return lax.pad(rows, rows.dtype.type(0),
                   ((0, 0, 0),) * (rows.ndim - 1) + ((0, pad, 0),))


class _BlockForm:
    """What the two kinds of layer that keep blocks share: where their pools
    lie in a program's cache (``ids``), their window, ``note`` (the engine's
    one callback) and the two writes, into a pool that holds a block's
    positions one after another — position-major ``[N, bs, H, Dp]``, a row
    pool ``[N, bs, Fp]`` — or as stated, ``[N, H, bs, D]``."""
    keeps_state = False
    select = None       # how many cached positions the layer's reads choose
    no_verify = None    # why the layer has no verify program, if it has none
    by_position = False     # a row pool: whatever order the K/V pools are in

    def __init__(self, layer, ids, window, block_size, max_blocks, note):
        self.layer, self.ids, self.window = layer, tuple(ids), window
        self.block_size, self.max_blocks, self.note = \
            block_size, max_blocks, note

    def write_prompt(self, caches, rows, table, j0, traced, position_major):
        """Write a prompt's ``rows`` — one array a pool of ``ids``, (Tb, H,
        D) or a latent layer's (Tb, F) — into the blocks ``table`` names
        from column ``j0`` on: the miss prefill's static 0, or the hit
        prefill's operand (``traced``), where a column past the table goes
        to the null block 0, in which padded garbage is harmless.
        ``caches`` is the program's list, updated in place."""
        import jax.numpy as jnp
        from jax import lax
        by_position = position_major or self.by_position
        bs, NB = self.block_size, self.max_blocks
        # as a pool holds a block's positions, once a layer: (H, Tb, D) for
        # one stored as stated, else (Tb, H, Dp) or (Tb, Fp)
        laid = [_to_lanes(r, caches[i]) if by_position
                else r.transpose(1, 0, 2) for i, r in zip(self.ids, rows)]
        for j in range(-(-rows[0].shape[0] // bs)):
            for i, a in zip(self.ids, laid):
                pool = caches[i]
                strip = a[j * bs:(j + 1) * bs] if by_position \
                    else a[:, j * bs:(j + 1) * bs]
                idx = j0 + j
                blk = jnp.where(idx < NB, jnp.take(
                    table, jnp.minimum(idx, NB - 1)), 0) if traced \
                    else table[idx]
                caches[i] = lax.dynamic_update_slice(
                    pool, strip[None].astype(pool.dtype),
                    (blk,) + (0,) * (pool.ndim - 1))

    def write_step(self, pool, blk, off, rows, position_major):
        """``pool`` with position ``off`` of block ``blk`` — (S,) or (S, Q)
        each — set to ``rows`` (S, H, D) or (S, Q, H, D); of a row pool, to
        ``rows`` (S, F)."""
        import jax.numpy as jnp
        rows = rows.astype(pool.dtype)
        if position_major or self.by_position:
            return pool.at[blk, off].set(_to_lanes(rows, pool))
        N, H, bs, D = pool.shape
        if H == 1 or D % KVLayout.LANES:
            return pool.at[blk, :, off].set(rows)
        # several heads of whole lanes: the grouped kernel reads such a
        # pool as [N, H * bs, D] (the same bytes), and a write through
        # that view leaves the compiler no other order to keep the pool in
        # than the one it rests in — written as [N, H, bs, D] it keeps
        # positions before heads inside the program and copies every pool
        # on the way in, for the kernel and on the way out
        # (tests/test_paged_attention.py)
        col = jnp.arange(H, dtype=off.dtype) * bs + off[..., None]
        return pool.reshape(N, H * bs, D).at[blk[..., None], col].set(
            rows).reshape(pool.shape)


class GroupedKV(_BlockForm):
    """A grouped-query layer: K and V of ``kv_heads x head_dim`` a position,
    two pools stored as :meth:`KVLayout.pool_shape` has them.  ``scale`` is
    the softmax scale the layer states (None: the kernels' own,
    ``head_dim ** -0.5``).  Its ``attend`` take ``(q, k, v)``."""

    def __init__(self, *facts, dtype, scale):
        super().__init__(*facts)
        self.dtype, self.scale = dtype, scale

    def allocate(self, num_blocks, device, pool_shape, state_rows):
        return [(pool_shape, self.dtype)] * 2

    def _picked(self, tables, pool, q_heads, position_major):
        # what the paged entry points pick, and the run kernel's step
        from ..kernels.flash_attention import (paged_attention_impl,
                                               paged_run_pages)
        at = (tables, pool, q_heads, self.window)
        self.note(self.layer, paged_attention_impl(*at, position_major),
                  paged_run_pages(*at, tables.shape[1], position_major))

    def suffix_attend(self, caches, table, ctx, j0, Tb, position_major):
        """``attend`` of the hit program: the suffix's K/V written from
        column ``j0`` (``ctx // block_size``, divided once a program) on,
        its queries over the slot's blocks (``paged_prefix_attention``)."""
        from ..kernels.flash_attention import paged_prefix_attention
        l, lv = self.ids

        def attend(q, k, v):             # (1, Tb, heads, D) each
            self.write_prompt(caches, (k[0], v[0]), table, j0, True,
                              position_major)
            attn = paged_prefix_attention(
                q.transpose(0, 2, 1, 3), caches[l], caches[lv],
                table, ctx, self.window, scale=self.scale,
                position_major=position_major)
            return attn.transpose(0, 2, 1, 3)
        return attend

    def step_attend(self, caches, blk, off, tables, positions,
                    position_major):
        """``attend`` of the two decode programs: one position a slot, K/V
        written to block ``blk`` at offset ``off``, attention through
        ``paged_decode_attention`` bounded by the layer's window."""
        from ..kernels.flash_attention import paged_decode_attention
        l, lv = self.ids

        def attend(q, k, v):             # (S, 1, heads, D) each
            ck = self.write_step(caches[l], blk, off, k[:, 0],
                                 position_major)
            cv = self.write_step(caches[lv], blk, off, v[:, 0],
                                 position_major)
            caches[l], caches[lv] = ck, cv
            self._picked(tables, ck, q.shape[2], position_major)
            return paged_decode_attention(
                q[:, 0], ck, cv, tables, positions, scale=self.scale,
                window=self.window, position_major=position_major)[:, None]
        return attend

    def verify_attend(self, caches, blk, off, tables, positions,
                      position_major):
        """``attend`` of the verify program: Q positions a slot, ``blk`` and
        ``off`` (S, Q).  (At Q == 1 it computes what :meth:`step_attend`
        does by another program; folding them changes both: ROADMAP D1.)"""
        from ..kernels.flash_attention import paged_verify_decode_attention
        l, lv = self.ids

        def attend(q, k, v):             # (S, Q, heads, D) each
            ck = self.write_step(caches[l], blk, off, k, position_major)
            cv = self.write_step(caches[lv], blk, off, v, position_major)
            caches[l], caches[lv] = ck, cv
            self._picked(tables, ck, q.shape[2], position_major)
            attn = paged_verify_decode_attention(
                q.transpose(0, 2, 1, 3), ck, cv, tables, positions,
                scale=self.scale, window=self.window,
                position_major=position_major)
            return attn.transpose(0, 2, 1, 3)
        return attend


class StatedRows(_BlockForm):
    """A layer that keeps the rows it states (``KVLayout.rows``), one row
    pool ``[N, bs, Fp]`` each: a latent layer's one row that is key and
    value both and, in a layer that chooses the keys it reads (``select``
    of them), its index key.  Its ``attend`` take ``(q_n, q_r, row, w_uk,
    w_uv, scale, index=None)`` and hold the latent cache's two forms: the
    unabsorbed one over a prompt's suffix, the absorbed one over a step."""
    by_position = True
    no_verify = (
        "no speculation over a latent cache yet: the verify program has no "
        "latent form (a block of drafted positions a slot, each with its "
        "own choice of keys in a layer that chooses them; ROADMAP M2)")

    def __init__(self, *facts, layout):
        super().__init__(*facts)
        self.layout, self.select = layout, layout.selects[self.layer]

    def allocate(self, num_blocks, device, pool_shape, state_rows):
        return [(self.layout.row_pool_shape(num_blocks, self.block_size,
                                            features, device), dtype)
                for features, dtype in self.layout.rows[self.layer]]

    def suffix_attend(self, caches, table, ctx, j0, Tb, position_major):
        """``attend`` of the hit program (``ServedLayer._block``): the
        suffix's rows written at ``ctx`` on, then its queries over the
        slot's strip in the unabsorbed form — every cached row a full layer
        keeps, its index keys with them; the blocks a window can touch of a
        sliding one.  (Its own ``ctx // bs`` a layer, not ``j0``: D1.)"""
        import jax.numpy as jnp
        from ..kernels.latent_attention import latent_prompt_attention
        bs, NB = self.block_size, self.max_blocks
        window, ids = self.window, self.ids

        def strip(pool, cols, features):
            """The slot's rows of the table columns ``cols``."""
            blocks = jnp.take(table, jnp.minimum(cols, NB - 1))
            return pool[blocks].reshape(-1, pool.shape[-1])[:, :features]

        def attend(q_n, q_r, row, w_uk, w_uv, scale, index=None):
            rows = (row[0],) if index is None else (row[0], index[2][0])
            self.write_prompt(caches, rows, table, ctx // bs, True,
                              position_major)
            if window is None:
                cols = jnp.arange(NB, dtype=jnp.int32)
            else:                   # from the window's first block on
                n = min(NB, -(-Tb // bs) + (window + bs - 2) // bs)
                cols = jnp.maximum(ctx - window + 1, 0) // bs \
                    + jnp.arange(n, dtype=jnp.int32)
            key_pos = (cols[:, None] * bs
                       + jnp.arange(bs, dtype=jnp.int32)[None]).reshape(-1)
            # a column past the table holds no key whatever it names
            key_pos = jnp.where(key_pos < NB * bs, key_pos,
                                jnp.iinfo(jnp.int32).max)
            q_pos = ctx + jnp.arange(Tb, dtype=jnp.int32)
            select = None if index is None else (
                index[0][0], index[1][0],
                strip(caches[ids[1]], cols, index[2].shape[-1]),
                self.select)
            return latent_prompt_attention(
                q_n[0], q_r[0], strip(caches[ids[0]], cols, row.shape[-1]),
                q_pos, key_pos, w_uk, w_uv, scale, window, select)[None]
        return attend

    def step_attend(self, caches, blk, off, tables, positions,
                    position_major):
        """``attend`` of the two decode programs: one position a slot, its
        row written to block ``blk`` at offset ``off``, then the absorbed
        form over the pool — the window of a sliding layer
        (:func:`paged_latent_decode`: a page read once, key and value
        both), and in a layer that chooses its keys the index key written
        beside the row, every cached index key scored, and the rows of the
        chosen positions alone read."""
        import jax
        from ..kernels import latent_attention as la
        window, ids = self.window, self.ids

        def attend(q_n, q_r, row, w_uk, w_uv, scale, index=None):
            r_kv = w_uk.shape[0]
            caches[ids[0]] = pool = self.write_step(
                caches[ids[0]], blk, off, row[:, 0], position_major)
            if index is None:
                self.note(self.layer, la.latent_decode_impl(row, pool))

                def read(q_abs):
                    return la.paged_latent_decode(
                        q_abs, pool, tables, positions, r_kv, scale, window)
            else:
                q_i, w_i, k_i = index
                caches[ids[1]] = keys = self.write_step(
                    caches[ids[1]], blk, off, k_i[:, 0], position_major)
                self.note(self.layer, la.index_select_impl(q_i, keys))
                with jax.named_scope("attn.index"):
                    chosen, valid = la.paged_index_select(
                        q_i[:, 0], w_i[:, 0], keys, tables, positions,
                        self.select)

                def read(q_abs):
                    return la.paged_sparse_latent(
                        q_abs, pool, chosen, valid, r_kv, scale)
            return la.absorbed_attention(q_n[:, 0], q_r[:, 0], w_uk, w_uv,
                                         read)[:, None]
        return attend

    def verify_attend(self, *where):
        raise MXNetError(self.no_verify)


class StateLeaves:
    """A layer that keeps a state of constant size a sequence INSTEAD of
    blocks (``KVLayout.states``): one array a leaf, a row a sequence.  The
    engine's ``_recur_prefill`` / ``_recur_decode`` hand them to the layer."""
    keeps_state = True
    select = None
    no_verify = (
        "no speculation over a recurrent state: a rejected draft token is "
        "rolled back by moving the position back, and a state that has "
        "consumed the token cannot be moved back (it would have to be "
        "snapshotted at every verify)")

    def __init__(self, layer, ids, leaves):
        self.layer, self.ids, self.leaves = layer, tuple(ids), leaves

    def allocate(self, num_blocks, device, pool_shape, state_rows):
        return [((state_rows,) + shape, dtype) for shape, dtype in self.leaves]


def cache_forms(layout, layers, block_size, max_blocks, note):
    """``(forms, n_pools)``: one form a layer of ``layout``, its kind read off
    what the model states for the layer, and how many pools a program's
    cache begins with: the layers' first rows in layer order, then their
    second rows (K pools, then V pools of a grouped-query model); the state
    layers' leaves follow.  ``layers`` are the model's ``serve_layers()`` (a
    grouped layer may state ``attn_scale``); ``note(layer, impl,
    run_step=None)`` is told what a layer's read picked as it is traced."""
    ids = {l: [] for l in layout.kv_layers}
    for n, (_, l) in enumerate(sorted(
            (j, l) for l in ids for j in range(len(layout.layer_rows(l))))):
        ids[l].append(n)
    n = n_pools = sum(map(len, ids.values()))
    forms = []
    for l in range(layout.num_layers):
        facts = (l, ids.get(l), layout.windows[l], int(block_size),
                 int(max_blocks), note)
        if layout.states[l] is not None:
            leaves = layout.states[l]
            forms.append(StateLeaves(l, range(n, n + len(leaves)), leaves))
            n += len(leaves)
        elif layout.rows[l] is not None:
            forms.append(StatedRows(*facts, layout=layout))
        else:
            forms.append(GroupedKV(
                *facts, dtype=layout.dtype,
                scale=getattr(layers[l], "attn_scale", None)))
    return forms, n_pools


def grouped_pool_shape(layout, forms, num_blocks, block_size, device):
    """:meth:`KVLayout.pool_shape` on ``device``; ``(None, False)`` for a
    model none of whose layers keeps K and V."""
    if not any(isinstance(f, GroupedKV) for f in forms):
        return None, False
    return layout.pool_shape(num_blocks, block_size, device)


def pool_layout(layout, num_blocks, block_size, pool_shape, position_major):
    """How the K and V pools are stored, for ``/programs``: ``"default"`` as
    stated, ``[N, H, bs, D]``, else the position-major ``[N, bs, H, Dp]``
    that rests in the layout the programs keep (``KVLayout.pool_shape``)."""
    if not position_major:
        return "default"
    return {"stored": "position_major", "shape": list(pool_shape),
            "stated": [num_blocks, layout.kv_heads, block_size,
                       layout.head_dim]}


class SnapshotPlan(NamedTuple):
    """What one prompt's prefill does with the state store
    (:meth:`BlockPool.allocate`): the snapshot row it starts from (None: a
    sequence's beginning) and, by boundary in tokens from the prompt's
    start, the rows it keeps the state in."""
    restore: Optional[int]
    keep: Dict[int, int]


NO_SNAPSHOTS = SnapshotPlan(None, {})


class BlockPool:
    """Refcounted allocator over ``num_blocks`` fixed-size KV blocks.

    ``num_blocks`` includes the reserved null block, so ``num_blocks - 1``
    blocks are allocatable. ``prefix_cache=False`` disables sharing (every
    allocation takes fresh blocks) but keeps the same accounting.

    **State snapshots** (a model with recurrent layers; ``snapshot_every``
    tokens, a multiple of ``block_size``, and ``snapshot_rows`` row ids from
    ``first_snapshot_row`` on).  A hash lookup finds a prefix's blocks, but
    a recurrent layer's state after that prefix cannot be rebuilt from
    them: it has to have been kept.  The prefill programs write the state
    at every multiple of ``snapshot_every`` into the row this pool names
    for it (the plan :meth:`allocate` returns), and the pool keeps the row
    under the cached block that ENDS at that boundary.  A row is named only
    where the prefix up to the boundary has been SEEN BEFORE — the block
    registered for it is an earlier prompt's — since a prefix one prompt
    ever sent would cost a row (megabytes to write) that nothing can hit:
    the first prompt with a prefix registers its blocks, the second finds
    them without a state, prefills whole and keeps the snapshots, the third
    hits.  A match is then the longest chain of
    cached blocks that ends at a block with a snapshot — cached blocks past
    the last snapshot are no hit — and the snapshot goes when its block's
    registration goes (LRU eviction, :meth:`invalidate`): a snapshot is
    never found without its blocks.  When the rows run out the snapshot
    used longest ago is dropped; its blocks stay cached and count for
    nothing past the last snapshot that is left.
    """

    def __init__(self, num_blocks: int, block_size: int, *,
                 prefix_cache: bool = True, model: str = "?",
                 snapshot_every: int = 0, snapshot_rows: int = 0,
                 first_snapshot_row: int = 0):
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (null block + 1), got {num_blocks}")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.prefix_cache = bool(prefix_cache)
        self._model = model
        if snapshot_every % self.block_size:
            raise ValueError(
                f"snapshot_every {snapshot_every} is no multiple of the "
                f"block size {block_size}")
        #: blocks between two state snapshots (0: the model keeps no state)
        self._snap_blocks = int(snapshot_every) // self.block_size \
            if prefix_cache and snapshot_rows else 0
        self._snap_row_ids = range(int(first_snapshot_row),
                                   int(first_snapshot_row)
                                   + int(snapshot_rows))
        self.snapshots_kept = self.snapshots_restored = 0
        self.snapshots_evicted = 0
        # device bytes behind one block (set by the owning engine once
        # its cache arrays exist) — lets stats() speak bytes, the unit
        # the device-memory plane attributes in (telemetry_device)
        self.block_bytes = 0
        self._lock = threading.RLock()
        self.hits = 0            # blocks reused from the prefix cache
        self.evictions = 0       # idle cached blocks reclaimed (LRU)
        self.cow_copies = 0      # copy_on_write calls that actually copied
        self.rewinds = 0         # rewind() calls that had work to do
        self.reset()

    # -- state ------------------------------------------------------------
    def reset(self) -> None:
        """Drop every allocation AND the prefix cache (weight update /
        watchdog restart: cached K/V no longer matches the params)."""
        with self._lock:
            self._ref = [0] * self.num_blocks
            self._free: deque = deque(range(1, self.num_blocks))
            self._hash: List[Optional[bytes]] = [None] * self.num_blocks
            self._by_hash: Dict[bytes, int] = {}
            # cached blocks with refcount 0, in LRU order (oldest first)
            self._idle: "OrderedDict[int, None]" = OrderedDict()
            # state snapshots: the row kept under a cached block, in the
            # order of last use (oldest first), and the rows not in use
            self._snap_of: "OrderedDict[int, int]" = OrderedDict()
            self._snap_free: deque = deque(self._snap_row_ids)
            self._update_gauges()

    @property
    def free_blocks(self) -> int:
        """Blocks available to a new allocation (truly free + evictable)."""
        with self._lock:
            return len(self._free) + len(self._idle)

    @property
    def blocks_in_use(self) -> int:
        """Blocks pinned by a nonzero refcount."""
        with self._lock:
            return (self.num_blocks - 1) - len(self._free) - len(self._idle)

    @property
    def cached_blocks(self) -> int:
        """Blocks currently registered in the prefix cache (any refcount)."""
        with self._lock:
            return len(self._by_hash)

    def refcount(self, block: int) -> int:
        with self._lock:
            return self._ref[block]

    def _update_gauges(self) -> None:
        _m.KV_BLOCKS_TOTAL.set(self.num_blocks - 1, model=self._model)
        _m.KV_BLOCKS_IN_USE.set(
            (self.num_blocks - 1) - len(self._free) - len(self._idle),
            model=self._model)

    # -- prefix hashing ---------------------------------------------------
    def chain_hashes(self, tokens: Sequence[int], limit: int) -> List[bytes]:
        """Chained blake2b digest per full block over ``tokens[:limit]``.

        ``hashes[i]`` commits to blocks ``0..i`` of the prompt, so a
        digest match implies the whole prefix matches, not just one
        block. 128-bit content digests make accidental aliasing of
        distinct prompts cryptographically impossible — unlike Python
        ``hash()``, where e.g. ``hash(-1) == hash(-2)`` collides.
        """
        bs = self.block_size
        out: List[bytes] = []
        h = ("mxtpu-kv:%d" % bs).encode()
        with _m.loop_step("hash", "serve.join.hash"):
            for i in range(int(limit) // bs):
                blk = b",".join(b"%d" % int(t)
                                for t in tokens[i * bs:(i + 1) * bs])
                h = hashlib.blake2b(h + b"|" + blk, digest_size=16).digest()
                out.append(h)
        return out

    def _match(self, hashes: Sequence[bytes], usable: int) -> List[int]:
        """Longest cached run of leading blocks, without increfing — for a
        model with state, the longest that ends at a snapshot."""
        if not self.prefix_cache:
            return []
        shared: List[int] = []
        for i in range(min(usable, len(hashes))):
            b = self._by_hash.get(hashes[i])
            if b is None:
                break
            shared.append(b)
        if self._snap_blocks:
            per = self._snap_blocks
            n = len(shared) // per * per
            while n and shared[n - 1] not in self._snap_of:
                n -= per
            del shared[n:]
        return shared

    @staticmethod
    def _usable_prefix_blocks(n: int, block_size: int) -> int:
        # At least one prompt token must stay outside the shared span so
        # the suffix prefill has a row to read the first logits from.
        return max(0, (int(n) - 1) // block_size)

    # -- allocation -------------------------------------------------------
    def can_admit(self, tokens: Sequence[int], n: int, reserve_tokens: int,
                  reserved_blocks: int = 0) -> bool:
        """Would :meth:`allocate` succeed right now? ``reserved_blocks``
        discounts capacity already promised to earlier admits in the same
        scheduling step."""
        with _m.loop_step("alloc", "serve.join.alloc"), self._lock:
            need = blocks_for(reserve_tokens, self.block_size)
            hashes = self.chain_hashes(tokens, (int(n) // self.block_size)
                                       * self.block_size)
            shared = self._match(
                hashes, self._usable_prefix_blocks(n, self.block_size))
            free = len(self._free) + len(self._idle) - int(reserved_blocks)
            # Idle blocks this request would share are pinned by the
            # share itself — they cannot double as reclaimable capacity
            # for the fresh tail.
            shared_idle = sum(1 for b in shared if self._ref[b] == 0)
            return free - shared_idle >= need - len(shared)

    def allocate(self, tokens: Sequence[int], n: int, reserve_tokens: int,
                 share: bool = True) -> Tuple[List[int], int, SnapshotPlan]:
        """Reserve blocks for a request with prompt ``tokens[:n]`` and a
        worst-case total of ``reserve_tokens`` positions.

        Returns ``(table, shared_tokens, plan)``: the ordered block table
        (length ``ceil(reserve_tokens / block_size)``), how many leading
        token positions already hold valid K/V from the prefix cache (always
        a multiple of ``block_size``), and the state rows of this prompt's
        prefill (:data:`NO_SNAPSHOTS` for a model without state and for
        ``share=False``): the caller hands it to the prefill it dispatches
        and, should that fail, back to :meth:`invalidate`. Raises
        :class:`MXNetError` when the
        pool cannot satisfy the reservation. ``share=False`` skips both
        prefix matching and registration (warmup traffic must not poison
        the cache).
        """
        n = int(n)
        need = blocks_for(reserve_tokens, self.block_size)
        if need < 1:
            raise ValueError(f"reserve_tokens must be >= 1, got {reserve_tokens}")
        with _m.loop_step("alloc", "serve.join.alloc"), self._lock:
            full = (n // self.block_size) * self.block_size
            hashes = self.chain_hashes(tokens, full) if share else []
            shared = self._match(
                hashes, self._usable_prefix_blocks(n, self.block_size))
            fresh_needed = need - len(shared)
            # Full capacity check BEFORE any mutation: idle blocks this
            # request shares are pinned by the share, so they must not
            # count toward the fresh tail — otherwise the shortfall
            # would only surface in _pop_free after refcounts were
            # already bumped, leaking the partial allocation.
            shared_idle = sum(1 for b in shared if self._ref[b] == 0)
            available = len(self._free) + len(self._idle) - shared_idle
            if available < fresh_needed:
                raise MXNetError(
                    f"kv pool exhausted: need {fresh_needed} blocks, "
                    f"{available} available "
                    f"({self.num_blocks - 1} total, block_size "
                    f"{self.block_size})")
            for b in shared:
                self._incref(b)
            table = list(shared)
            for _ in range(fresh_needed):
                b = self._pop_free()
                self._ref[b] = 1
                table.append(b)
            # Register this prompt's remaining full blocks so later
            # requests with the same prefix share them. The worker
            # prefills immediately after allocate() (same thread), so the
            # registered blocks hold valid K/V before any later lookup.
            if self.prefix_cache and share:
                for i in range(len(shared), len(hashes)):
                    if hashes[i] not in self._by_hash:
                        self._by_hash[hashes[i]] = table[i]
                        self._hash[table[i]] = hashes[i]
            if shared:
                self.hits += len(shared)
                _m.PREFIX_CACHE_HITS.inc(len(shared), model=self._model)
            plan = self._plan_snapshots(hashes, shared, table) \
                if self._snap_blocks and share else NO_SNAPSHOTS
            self._update_gauges()
            return table, len(shared) * self.block_size, plan

    def _plan_snapshots(self, hashes, shared, table) -> SnapshotPlan:
        """After a match of ``shared`` blocks of a prompt whose full blocks
        hash to ``hashes`` and lie in ``table``: the row of the snapshot the
        match ends at, and a row for the state at every boundary past it
        that an EARLIER prompt's registered block ends at (the state after a
        prefix is the prefix's, whoever computes it) — none from the first
        boundary on whose block is this prompt's own: nobody has sent that
        prefix before."""
        per = self._snap_blocks
        restore = None
        if shared:
            restore = self._snap_of[shared[-1]]
            self._snap_of.move_to_end(shared[-1])
            self.snapshots_restored += 1
            _m.STATE_SNAPSHOTS.inc(model=self._model, event="restored")
        keep = {}
        for j in range(len(shared) // per + 1, len(hashes) // per + 1):
            b = self._by_hash[hashes[j * per - 1]]
            if b == table[j * per - 1]:
                break
            if b in self._snap_of:
                continue
            if not self._snap_free:             # the one used longest ago
                self._drop_snapshot(next(iter(self._snap_of)))
            self._snap_of[b] = keep[j * per * self.block_size] = \
                self._snap_free.popleft()
            self.snapshots_kept += 1
            _m.STATE_SNAPSHOTS.inc(model=self._model, event="kept")
        return SnapshotPlan(restore, keep)

    def _drop_snapshot(self, block: int) -> None:
        row = self._snap_of.pop(block, None)
        if row is not None:
            self._snap_free.append(row)
            self.snapshots_evicted += 1
            _m.STATE_SNAPSHOTS.inc(model=self._model, event="evicted")

    @property
    def snapshots_in_use(self) -> int:
        with self._lock:
            return len(self._snap_of)

    def release(self, table: Sequence[int]) -> None:
        """Decref every block in ``table``. Blocks reaching refcount 0
        return to the free list, unless cached — those stay evictable in
        LRU order for future prefix hits."""
        with _m.loop_step("alloc", "serve.join.alloc"), self._lock:
            for b in table:
                if b == NULL_BLOCK:
                    continue
                if self._ref[b] <= 0:
                    raise MXNetError(f"double free of kv block {b}")
                self._ref[b] -= 1
                if self._ref[b] == 0:
                    if self._hash[b] is not None:
                        self._idle[b] = None
                        self._idle.move_to_end(b)
                    else:
                        self._free.append(b)
            self._update_gauges()

    def invalidate(self, blocks: Sequence[int],
                   plan: SnapshotPlan = NO_SNAPSHOTS) -> None:
        """Unregister ``blocks`` from the prefix cache without touching
        refcounts. For blocks whose K/V never became valid — a prefill
        that failed after :meth:`allocate` had already registered them —
        so a later request with the same prefix prefills cold instead of
        "hitting" garbage. Unregistered blocks are a no-op.  The snapshots
        of that prefill's ``plan`` were its to write: they go too."""
        with self._lock:
            for b in blocks:
                if b != NULL_BLOCK:
                    self._evict_hash(b)
            rows = set(plan.keep.values())
            for b in [b for b, r in self._snap_of.items() if r in rows]:
                self._drop_snapshot(b)

    def copy_on_write(self, block: int) -> int:
        """Private handle for a block the caller wants to mutate. Returns
        ``block`` unchanged when exclusively owned; otherwise decrefs it,
        allocates a fresh block (refcount 1), and returns the new id — the
        caller must copy the device contents before writing."""
        with self._lock:
            if self._ref[block] <= 0:
                raise MXNetError(f"copy_on_write of unreferenced block {block}")
            if self._ref[block] == 1 and self._hash[block] is None:
                return block
            if self._ref[block] == 1:
                # Exclusively owned but published in the prefix cache:
                # unpublish instead of copying — readers arriving later
                # simply miss.
                self._evict_hash(block)
                return block
            if not self._free and not self._idle:
                raise MXNetError("kv pool exhausted during copy_on_write")
            self._ref[block] -= 1
            new = self._pop_free()
            self._ref[new] = 1
            self.cow_copies += 1
            self._update_gauges()
            return new

    def rewind(self, table: Sequence[int], keep_tokens: int) -> List[int]:
        """Prepare ``table`` for overwriting every position
        ``>= keep_tokens`` (speculative-decode rollback: rejected draft
        positions will be re-written by the next dispatch).

        No block is ever freed — the reservation stays intact, and blocks
        holding only kept positions (the shared prefix among them) are
        untouched.  Blocks in the dirty span that are shared (refcount
        > 1) or published in the prefix cache get :meth:`copy_on_write`
        treatment so the overwrite cannot corrupt a neighbor's view;
        the returned table carries any replacement ids.

        The serving flow only ever writes past the prompt, and only full
        immutable prompt blocks are shared/published, so the COW branch
        is a contract guard rather than a hot path.  A shared block that
        also holds kept positions cannot be rolled back on the host alone
        (the private copy would lose the kept K/V) — that state is
        unreachable through the engine and raises.
        """
        keep_tokens = max(0, int(keep_tokens))
        bs = self.block_size
        with self._lock:
            out = list(table)
            first = keep_tokens // bs   # first block with a dirty position
            touched = False
            for i in range(first, len(out)):
                b = out[i]
                if b == NULL_BLOCK:
                    continue
                if self._ref[b] <= 1 and self._hash[b] is None:
                    continue
                if i * bs < keep_tokens:
                    raise MXNetError(
                        f"rewind would copy-on-write block {b} holding "
                        f"kept positions (keep={keep_tokens}); decode "
                        f"writes must never land in shared prompt blocks")
                out[i] = self.copy_on_write(b)
                touched = True
            if touched:
                self.rewinds += 1
                self._update_gauges()
            return out

    # -- internals --------------------------------------------------------
    def _incref(self, b: int) -> None:
        self._ref[b] += 1
        if self._ref[b] == 1:
            self._idle.pop(b, None)

    def _pop_free(self) -> int:
        if self._free:
            return self._free.popleft()
        if self._idle:
            b, _ = self._idle.popitem(last=False)  # LRU: oldest idle first
            self._evict_hash(b)
            self.evictions += 1
            _m.PREFIX_CACHE_EVICTIONS.inc(model=self._model)
            return b
        raise MXNetError("kv pool exhausted")

    def _evict_hash(self, b: int) -> None:
        h = self._hash[b]
        if h is not None and self._by_hash.get(h) == b:
            del self._by_hash[h]
        self._hash[b] = None
        self._drop_snapshot(b)      # a snapshot goes with its block

    def stats(self) -> Dict[str, object]:
        with self._lock:
            total = self.num_blocks - 1
            in_use = total - len(self._free) - len(self._idle)
            out = {
                "kv_block_size": self.block_size,
                "kv_blocks_total": total,
                "kv_blocks_in_use": in_use,
                "kv_blocks_cached_idle": len(self._idle),
                "kv_utilization": (in_use / total) if total else 0.0,
                "prefix_cache": self.prefix_cache,
                "prefix_cache_hits": self.hits,
                "prefix_cache_evictions": self.evictions,
                "rewinds": self.rewinds,
            }
            if self._snap_row_ids:
                out.update({
                    "state_snapshot_rows": len(self._snap_row_ids),
                    "state_snapshots_in_use": len(self._snap_of),
                    "state_snapshots_kept": self.snapshots_kept,
                    "state_snapshots_restored": self.snapshots_restored,
                    "state_snapshots_evicted": self.snapshots_evicted})
            if self.block_bytes:
                out["kv_bytes_total"] = total * self.block_bytes
                out["kv_bytes_in_use"] = in_use * self.block_bytes
            return out
