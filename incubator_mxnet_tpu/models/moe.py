"""Mixture-of-Experts FFN with expert parallelism (reference lineage:
the Switch/GShard MoE layer — the reference repo itself predates MoE, so
this is a beyond-parity capability like ring attention, SURVEY §5.7;
built from the same op surface as every model here).

TPU-first design:
  * experts are STACKED parameters — w1 (E, C, H), w2 (E, H, C) — so
    expert parallelism is nothing but a sharding rule
    (``ep_rules('expert')``: PartitionSpec('expert', ...) on the stacked
    axis).  GSPMD then inserts the dispatch all-to-alls over ICI by
    itself; there is no hand-written collective (the scaling-book
    recipe: annotate, let XLA place the communication);
  * routing is the capacity-based GShard dispatch: one-hot
    dispatch/combine tensors and three einsums — dense, static-shaped,
    MXU-friendly; no sorts or dynamic shapes inside the program;
  * top-k (k=1 Switch, k=2 GShard default) with renormalized gates and
    rank-ordered capacity claims; overflowing tokens are DROPPED
    (combine weight 0) exactly like the reference implementations — the
    load-balancing auxiliary loss keeps that rare;
  * the auxiliary load-balancing loss (Switch eq. 4) is returned
    alongside the output so the training loss can add it.
"""
from __future__ import annotations

import contextlib
import math
import threading

import numpy as _np

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..kernels.grouped_experts import (DECODE_PAIRS, group_pairs,
                                       held_experts_impl,
                                       held_experts_pallas,
                                       held_experts_route,
                                       held_experts_sorted)
from ..ndarray.ndarray import _invoke

__all__ = ["MoEFFN", "MoELoss", "ep_rules", "route_token_choice",
           "held_experts_ffn", "held_experts_impl"]


# ---------------------------------------------------------------------------
# token-choice routing over the PUBLISHED expert count, for a layer that
# holds a share of the experts (one chip of an expert-parallel group).
# Nothing is dropped and nothing depends on how many tokens arrive, so
# prefill and decode route alike.  The capacity path below stays for
# training until ROADMAP D9 retires it.
# ---------------------------------------------------------------------------

def route_token_choice(logits, bias, k, route_norm=True, route_scale=1.0,
                       score="sigmoid"):
    """Token-choice routing: ``logits`` (T, E) float32 over ALL the
    published experts.  Returns ``(idx (T, k) int32, w (T, k) float32)``:
    each token's ``k`` experts and the weights their outputs are summed
    with.

    ``score="sigmoid"``: the scores are the logits' sigmoids, ``bias``
    (E,) is added for the choice only, and the weights are the chosen
    scores themselves, normalised to sum to 1 when ``route_norm``, times
    ``route_scale``.  ``score="softmax"``: the ``k`` largest logits are
    chosen (no bias: pass None) and the weights are the softmax over the
    CHOSEN logits — which is the softmax over all E, top ``k``,
    renormalised — times ``route_scale``."""
    import jax
    import jax.numpy as jnp
    logits = logits.astype(jnp.float32)
    if score == "softmax":
        if bias is not None:
            raise MXNetError("softmax-scored routing takes no choice bias")
        top, idx = jax.lax.top_k(logits, k)
        return idx.astype(jnp.int32), jax.nn.softmax(top, -1) * route_scale
    if score != "sigmoid":
        raise MXNetError(f"no such routing score: {score!r}")
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32)[None], k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * route_scale


def _glu(x, w_gate, w_up, w_down, act="silu"):
    """``W_down(act(W_gate x) * W_up x)`` with ``(in, out)`` matrices,
    ``act`` ``"silu"`` (SwiGLU) or ``"relu"`` (ReGLU): products accumulate
    in float32, the activation is float32, operands of the second product
    are of ``x``'s type.  Returns float32."""
    import jax
    import jax.numpy as jnp
    gate = {"silu": jax.nn.silu, "relu": jax.nn.relu}[act]
    g = jnp.dot(x, w_gate, preferred_element_type=jnp.float32)
    u = jnp.dot(x, w_up, preferred_element_type=jnp.float32)
    mid = (gate(g) * u).astype(x.dtype)
    return jnp.dot(mid, w_down, preferred_element_type=jnp.float32)


def _swiglu(x, w_gate, w_up, w_down):
    return _glu(x, w_gate, w_up, w_down, "silu")


#: the sets this thread's callers of :func:`traced_expert_impls` collect into
_tracing = threading.local()


@contextlib.contextmanager
def traced_expert_impls():
    """Collects what :func:`held_experts_impl` answered for every expert
    layer this thread traces inside the block: a set of ``"pallas"`` /
    ``"pallas_sorted"`` / ``"lax_loop"`` (empty for a model without one).
    The serving engine keeps it with each program it traces."""
    seen = set()
    sets = _tracing.__dict__.setdefault("sets", [])
    sets.append(seen)
    try:
        yield seen
    finally:
        sets.remove(seen)


def held_experts_ffn(x, idx, w, held, w_gate, w_up, w_down, live=None,
                     tile=None, act="silu"):
    """The part of ``sum_k w_k * Expert_k(x)`` that the experts HELD here
    give: ``held = (first, count)`` names the published experts
    ``first .. first + count - 1``, whose gated-unit matrices are the
    stacked ``w_gate``/``w_up`` (count, d, f) and ``w_down`` (count, f, d),
    the gate's activation ``act`` (``"silu"`` or ``"relu"``).
    ``x`` (T, d), ``idx``/``w`` (T, k) from :func:`route_token_choice`,
    ``live`` (T,) bool marks real tokens (padding and free slots route
    nowhere).  Returns ``(y (T, d) float32, (pairs, pairs_held,
    experts_touched))``, the three counts int32 scalars.

    One grouped product, static shapes, no token dropped: the T*k
    (token, expert) pairs are counting-sorted by expert (pairs of experts
    held elsewhere last), and a loop whose trip count is a runtime value
    walks the held pairs ``tile`` rows at a time — one step per
    (expert, tile of its rows), each reading that expert's matrices once.
    An expert no token chose is not visited and its weights are not
    read; work follows the pairs held, never the published count.

    On a TPU the product is ONE Pallas kernel (``kernels/grouped_experts.py``;
    :func:`held_experts_impl` says which): a decode-shaped call's grid walks
    the touched experts with all the tokens, a prompt's walks the sorted
    rows a tile at a time, and either way the next expert's matrices arrive
    while the last one's rows are multiplied.  The loop is the CPU's path,
    the kernels' reference and what a ``tile`` asks for."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    T, k = idx.shape
    d = x.shape[-1]
    P = T * k
    first, count = int(held[0]), int(held[1])
    impl, interpret = ("lax_loop", False) if tile \
        else held_experts_route(x, w_gate, P)
    for seen in getattr(_tracing, "sets", ()):
        seen.add(impl)
    if impl == "pallas":
        on = (idx >= first) & (idx < first + count)
        n_live = jnp.asarray(T, jnp.int32)
        if live is not None:
            on = on & live[:, None]
            n_live = jnp.sum(live, dtype=jnp.int32)
        y, pairs_held, touched = held_experts_pallas(
            x, jnp.where(on, idx - first, -1), w, w_gate, w_up, w_down, act,
            interpret=interpret)
        return y, (n_live * k, pairs_held, touched)
    if impl == "pallas_sorted":
        # one jitted body for every layer of a program that calls alike
        # (what it would not see a second time is recorded above)
        return held_experts_sorted(x, idx, w, (first, count), w_gate, w_up,
                                   w_down, live, act, interpret=interpret)
    tile = min(int(tile or (128 if P >= DECODE_PAIRS else 32)), P)
    is_held, n_live, n, starts, dest, src = group_pairs(
        idx, (first, count), live)
    xs = x[src // k]                                             # (P, d)
    n_e = n[:count]
    chunks = (n_e + tile - 1) // tile
    ends = jnp.cumsum(chunks)
    row_ids = jnp.arange(tile, dtype=jnp.int32)

    def step(i, ys):
        e = jnp.sum(i >= ends, dtype=jnp.int32)
        row0 = starts[e] + (i - (ends[e] - chunks[e])) * tile
        r0 = jnp.minimum(row0, P - tile)
        rows = lax.dynamic_slice(xs, (r0, 0), (tile, d))
        out = _glu(rows,
                   lax.dynamic_index_in_dim(w_gate, e, 0, False),
                   lax.dynamic_index_in_dim(w_up, e, 0, False),
                   lax.dynamic_index_in_dim(w_down, e, 0, False), act)
        at = r0 + row_ids
        mine = (at >= row0) & (at < jnp.minimum(row0 + tile,
                                                starts[e] + n_e[e]))
        old = lax.dynamic_slice(ys, (r0, 0), (tile, d))
        return lax.dynamic_update_slice(
            ys, jnp.where(mine[:, None], out, old), (r0, 0))

    ys = lax.fori_loop(0, ends[-1], step, jnp.zeros((P, d), jnp.float32))
    wk = jnp.where(is_held.reshape(T, k), w, 0.0)
    y = jnp.sum(ys[dest].reshape(T, k, d) * wk[..., None], axis=1)
    counts = (n_live * k, jnp.sum(n_e, dtype=jnp.int32),
              jnp.sum(n_e > 0, dtype=jnp.int32))
    return y, counts


def _moe_dispatch(logits, k, capacity, valid=None):
    """GShard routing over one GROUP of g tokens: returns (dispatch
    (g, E, Cap) f32, combine (g, E, Cap) f32, aux scalar).  Rank r
    claims capacity after ranks < r; tokens keep arrival order within a
    rank.  Vmapped over groups — capacity is per group, so the
    dispatch/combine tensors stay linear in total token count.

    ``valid`` (g,) 0/1 marks real tokens: invalid (padding) tokens
    claim NO expert capacity, produce zero output, and are excluded
    from the aux-loss statistics — without it, padded positions compete
    real tokens out of their expert buffers."""
    import jax
    import jax.numpy as jnp
    g, E = logits.shape
    raw = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    vals, idx = jax.lax.top_k(raw, k)                  # (g, k)
    w = vals / jnp.maximum(jnp.sum(vals, -1, keepdims=True), 1e-9)
    vfl = None if valid is None else valid.astype(jnp.float32)

    dispatch = jnp.zeros((g, E, capacity), jnp.float32)
    combine = jnp.zeros((g, E, capacity), jnp.float32)
    counts = jnp.zeros((E,), jnp.int32)
    top1 = None
    for r in range(k):
        onehot = jax.nn.one_hot(idx[:, r], E, dtype=jnp.int32)  # (g, E)
        if vfl is not None:     # padding claims nothing, routes nowhere
            onehot = onehot * vfl.astype(jnp.int32)[:, None]
        if r == 0:
            top1 = onehot
        # this token's slot in its expert's buffer: earlier tokens of
        # the same rank + everything claimed by lower ranks
        pos = jnp.cumsum(onehot, axis=0) - onehot + counts[None]
        pos_tok = jnp.sum(pos * onehot, axis=-1)                # (g,)
        keep = pos_tok < capacity
        slot = jax.nn.one_hot(pos_tok, capacity, dtype=jnp.float32)
        d_r = (onehot.astype(jnp.float32)[:, :, None] * slot[:, None, :]
               * keep.astype(jnp.float32)[:, None, None])
        dispatch = dispatch + d_r
        combine = combine + d_r * w[:, r][:, None, None]
        counts = counts + jnp.sum(onehot, axis=0)

    # Switch aux loss: E * sum_e mean_gate_e * fraction_top1_e,
    # statistics over VALID tokens only
    if vfl is None:
        me = jnp.mean(raw, axis=0)
        ce = jnp.mean(top1.astype(jnp.float32), axis=0)
    else:
        n = jnp.maximum(jnp.sum(vfl), 1.0)
        me = jnp.sum(raw * vfl[:, None], axis=0) / n
        ce = jnp.sum(top1.astype(jnp.float32), axis=0) / n
    aux = E * jnp.sum(me * ce)
    return dispatch, combine, aux


class MoEFFN(HybridBlock):
    """Drop-in positionwise FFN with E experts.

    Forward returns ``(out (B, T, C), aux_loss scalar)``; add
    ``aux_weight * aux_loss`` to the training loss (Switch uses 1e-2).
    ``capacity_factor`` scales each expert's token buffer
    (ceil(cf * S * k / E)); overflow is dropped like the reference
    implementations."""

    def __init__(self, units, hidden_size, num_experts, top_k=2,
                 capacity_factor=1.25, group_size=256, activation="gelu",
                 dtype=_np.float32, **kwargs):
        super().__init__(**kwargs)
        if top_k < 1 or top_k > num_experts:
            raise MXNetError(f"top_k={top_k} must be in [1, num_experts]")
        self._units = units
        self._hidden = hidden_size
        self._E = num_experts
        self._k = top_k
        self._cf = capacity_factor
        self._group = group_size
        self._act = activation
        with self.name_scope():
            self.router = nn.Dense(num_experts, flatten=False,
                                   use_bias=False, in_units=units)
            self.w1 = self.params.get(
                "w1", shape=(num_experts, units, hidden_size), dtype=dtype)
            self.b1 = self.params.get(
                "b1", shape=(num_experts, hidden_size), dtype=dtype,
                init="zeros")
            self.w2 = self.params.get(
                "w2", shape=(num_experts, hidden_size, units), dtype=dtype)
            self.b2 = self.params.get(
                "b2", shape=(num_experts, units), dtype=dtype,
                init="zeros")

    def hybrid_forward(self, F, x, valid=None, w1=None, b1=None,
                       w2=None, b2=None):
        logits = self.router(x)                       # (B, T, E)
        E, k, cf, act = self._E, self._k, self._cf, self._act
        group = self._group

        def run(xv, lg, w1v, b1v, w2v, b2v, vv=None):
            import functools
            import jax
            import jax.numpy as jnp
            B, T, C = xv.shape
            S = B * T
            # route within fixed-size groups (GShard): capacity is per
            # group, so dispatch/combine memory is O(S * g), linear in
            # token count — never O(S^2)
            g = min(group or S, S)
            while S % g:              # largest divisor <= requested size
                g -= 1
            G = S // g
            capacity = max(1, int(math.ceil(cf * g * k / E)))
            fn = functools.partial(_moe_dispatch, k=k, capacity=capacity)
            if vv is None:
                dispatch, combine, aux = jax.vmap(fn)(lg.reshape(G, g, E))
            else:
                dispatch, combine, aux = jax.vmap(fn)(
                    lg.reshape(G, g, E),
                    valid=vv.reshape(G, g).astype(jnp.float32))
            aux = jnp.mean(aux)       # equal groups: mean == global
            xs = xv.reshape(G, g, C)
            # dispatch -> per-expert buffers -> FFN -> combine back
            ein = dispatch.astype(xv.dtype)
            expert_in = jnp.einsum("gsec,gsm->gecm", ein, xs)
            h = jnp.einsum("gecm,emh->gech", expert_in, w1v) \
                + b1v[None, :, None, :]
            h = jax.nn.gelu(h) if act == "gelu" else jax.nn.relu(h)
            y = jnp.einsum("gech,ehm->gecm", h, w2v) \
                + b2v[None, :, None, :]
            out = jnp.einsum("gsec,gecm->gsm",
                             combine.astype(xv.dtype), y)
            return out.reshape(B, T, C), aux

        if valid is None:
            out, aux = _invoke(run, [x, logits, w1, b1, w2, b2],
                               name="moe_ffn")
        else:
            out, aux = _invoke(
                lambda xv, lg, w1v, b1v, w2v, b2v, vv:
                    run(xv, lg, w1v, b1v, w2v, b2v, vv),
                [x, logits, w1, b1, w2, b2, valid], name="moe_ffn")
        return out, aux


class MoELoss(HybridBlock):
    """Wrap a base loss to add the router's load-balancing term: takes
    ``(out, aux, *labels)`` — the output signature of any MoE model
    (e.g. ``GPTModel(moe_experts=E)``) — and returns
    ``mean(base(out, *labels)) + aux_weight * aux`` (Switch uses
    aux_weight 1e-2).  Drop-in loss block for Trainer/SPMDTrainer."""

    def __init__(self, base, aux_weight=1e-2, **kwargs):
        super().__init__(**kwargs)
        self._aux_weight = aux_weight
        with self.name_scope():
            self.base = base

    def hybrid_forward(self, F, out, aux, *labels):
        return self.base(out, *labels).mean() + self._aux_weight * aux


def ep_rules(expert_axis="expert", block=None):
    """Expert-parallel sharding: the stacked expert axis of every expert
    parameter shards over the mesh's expert axis; GSPMD inserts the
    token all-to-alls.  Compose with tp/dp rules by concatenation.

    With ``block`` (a MoEFFN, or any Block containing them) the rules
    are derived from the ACTUAL parameter names — use this whenever the
    layers were built with a custom ``prefix=``, which the default
    auto-prefix regexes cannot see (they would silently replicate the
    experts)."""
    from jax.sharding import PartitionSpec as P
    from ..parallel.spmd import exact_rule
    specs = {"w1": P(expert_axis, None, None),
             "b1": P(expert_axis, None),
             "w2": P(expert_axis, None, None),
             "b2": P(expert_axis, None)}
    if block is not None:
        rules = []
        blocks = []
        block.apply(lambda b: blocks.append(b)
                    if isinstance(b, MoEFFN) else None)
        if not blocks:
            raise MXNetError("ep_rules(block=...): no MoEFFN found")
        for b in blocks:
            rules.extend(exact_rule(getattr(b, short), spec)
                         for short, spec in specs.items())
        return rules
    return [(rf"moeffn\d+_{short}$", spec)
            for short, spec in specs.items()]
