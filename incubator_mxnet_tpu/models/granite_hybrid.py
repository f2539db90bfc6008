"""Granite 4.0-H decoder (``model_type: granitemoehybrid``; the published
description is the ``ibm-granite/granite-4.0-h-micro`` config.json keys and
modelling code): pre-norm blocks whose sequence mixing is a Mamba-2 state
space in nine layers of ten — a float32 matrix a head, updated every token,
and a short causal convolution's tail: state of a constant size, no keys —
and position-free grouped-query attention on heads of 64 in the tenth; a
SwiGLU MLP in every layer; four scalars where other decoders have none; a
head that IS the embedding.

``layer_types`` says which layer is which.  For input ids::

    h = embedding_multiplier * E[ids]
    h = h + residual_multiplier * Mix(rmsnorm(h; w1))
    h = h + residual_multiplier * W_down (silu(y W_gate) * (y W_up)),  y = rmsnorm(h; w2)

    Mamba-2:
    [z | xBC | dt] = x W_in                    # d -> inner + (inner + 2 N) + H
                                               # (held as its three groups of columns)
    xBC <- silu(causal depthwise conv, width 4, with bias);  xBC -> x_h (H x P), B (N), C (N)
    dt_h = softplus(dt_h + dt_bias_h);   a_h = exp(-exp(A_log_h) dt_h)
    S_h <- a_h S_h + B (dt_h x_h)^T;     y_h = S_h^T C + D_h x_h      # float32
    Mix = rmsnorm(y * silu(z); w_n) W_out      # over all H x P features

    attention:
    q, k, v = x W_q, x W_k, x W_v;   query head n reads KV head n // (Hq / Hkv)
    Mix = softmax(attention_multiplier q k^T, causal) v W_o     # no position signal

    logits = rmsnorm(h; w_f) E^T / logits_scaling

Constructor arguments are the source's keys.  The column order inside
``W_in`` is ``[z | xBC | dt]``, the gate is applied BEFORE the inner norm,
``dt`` is not clamped above, ``D`` is a scalar a head, ``B`` and ``C`` are
shared by all heads (``mamba_n_groups`` 1: another count raises), the state is
float32.  ``num_local_experts`` other than 0 raises: the sibling with
experts is another model.

**Runs of layers.**  Forty layers unrolled in each of a dozen programs take
longer to compile than a server may take to start, so a run of consecutive
Mamba layers is ONE :class:`~.decoder.ServedLayer`
(:class:`GraniteMambaRun`): its parameters are stacked on a leading axis
(``(n, ...)`` each), its state leaves are ``(n, N, H * P)`` and ``(n, K - 1,
conv)`` a sequence, and its ``_block`` is a ``lax.scan`` over its ``n`` layers,
mixer and MLP both.  The published forty are nine served layers here: runs of
5, 9, 9, 9 and 4 around the four attention layers.  A run states
``state_in_place``: the decode programs hand it the engine's whole leaves and
:func:`~..kernels.mamba2.ssd_step_rows` updates the LIVE slots' rows where
they lie — a slot nobody is on is not read at all: the run makes the step's
list of live rows once (:func:`~..kernels.mamba2.step_work_list`) and every
layer's call walks it — because a program that sliced 48 rows of 19 MB out
and wrote them back would move the state twice more than the recurrence
does.

**Heads of 64.**  A KV head of 64 features would rest on half a lane tile,
so the attention layer keeps two KV heads side by side as ONE head of 128
(``kv_layout``: ``num_key_value_heads / 2`` heads of ``2 * head_dim``): keys
and values are stored as they come out of the projection, 64 features a
head and nothing padded; a query is written into its own KV head's half of
128 with zeros in the other, so its scores are its own, and of the 128
features that come back it keeps that half.  The cache, the pool and the
paged kernels see a grouped-query layer on heads of 128.
"""
from __future__ import annotations

import functools

from ..base import MXNetError
from .decoder import (ServedDecoder, ServedLayer, causal_conv, rms_norm,
                      tail_after)
from .moe import _glu

__all__ = ["GraniteMambaRun", "GraniteAttentionLayer", "GraniteHybridModel",
           "layer_runs"]


def layer_runs(layer_types):
    """``layer_types`` -> the served layers: ``[("mamba", n) | ("attention",
    1), ...]`` — a run of ``n`` consecutive Mamba layers is one."""
    runs = []
    for kind in layer_types:
        if kind not in ("mamba", "attention"):
            raise MXNetError(f"no such layer type: {kind!r}")
        if kind == "mamba" and runs and runs[-1][0] == "mamba":
            runs[-1] = ("mamba", runs[-1][1] + 1)
        else:
            runs.append((kind, 1))
    return runs


def _mlp(layer, h, w):
    """``h + residual_multiplier * MLP(rmsnorm(h))`` with the weights
    ``w(name)``."""
    import jax
    import jax.numpy as jnp
    c = layer._c
    with jax.named_scope("mlp.shared"):
        y = rms_norm(h, w("post_attention_layernorm"), c["rms_norm_eps"])
        m = _glu(y, w("mlp_gate"), w("mlp_up"), w("mlp_down"))
        return h + (c["residual_multiplier"] * m).astype(h.dtype)


def _mlp_shapes(c, lead=()):
    d, f = c["hidden_size"], c["shared_intermediate_size"]
    return {"input_layernorm": lead + (d,),
            "post_attention_layernorm": lead + (d,),
            "mlp_gate": lead + (d, f), "mlp_up": lead + (d, f),
            "mlp_down": lead + (f, d)}


class GraniteMambaRun(ServedLayer):
    """``n`` consecutive Mamba-2 blocks (mixer, then MLP) as one served
    layer: every parameter ``(n, ...)``, the blocks a ``lax.scan``."""

    #: the decode programs hand this layer the engine's whole state leaves
    #: (its sequences' rows are the first ``B``) and take them back
    state_in_place = True

    def __init__(self, cfg, n, **kwargs):
        self._c = c = cfg
        #: Mamba layers in the run (``mxtpu_ssm_*`` count by them)
        self.ssm_layers = n = int(n)
        self._conv = c["mamba_n_heads"] * c["mamba_d_head"] \
            + 2 * c["mamba_d_state"]
        d, N, H = c["hidden_size"], c["mamba_d_state"], c["mamba_n_heads"]
        inner = H * c["mamba_d_head"]
        conv = inner + 2 * N
        shapes = _mlp_shapes(c, (n,))
        # W_in's three groups of columns are three arrays: 8,512 columns
        # are no whole number of lane tiles, and a stacked matrix that is
        # not gets copied whole ahead of the loop that slices it
        shapes.update(
            in_proj_z=(n, d, inner), in_proj_xbc=(n, d, conv),
            in_proj_dt=(n, d, H), conv1d=(n, c["mamba_d_conv"], conv),
            conv_bias=(n, conv), dt_bias=(n, H), A_log=(n, H), D=(n, H),
            norm=(n, inner), out_proj=(n, inner, d))
        #: what a sequence keeps of this run: the matrices (the state's N
        #: coordinates on the rows, the heads' features side by side) and
        #: the convolution's tails, layer after layer in one flat vector
        #: (a leaf's trailing axes are where a device's layout is decided:
        #: (K - 1, conv) at the end got the leaf transposed whole on its
        #: way into the loop over the layers), float32 both
        self.state_shapes = (
            ((n, N, inner), "float32"),
            ((n * (c["mamba_d_conv"] - 1) * conv,), "float32"))
        super().__init__(shapes, c["dtype"], c["grad_req"], None, **kwargs)

    def _mixer(self, x, w, S, tail, leaf, i, on, every, work=None):
        """One layer's mixer over x (B, T, d) normed.  A prompt (``leaf``
        None): from the rows' state ``S`` (B, N, H * P) and ``tail``;
        returns ``(mix, S', tail', snapshots)``.  One token a row (``leaf``
        the engine's whole matrices' leaf, ``i`` this layer's index in it,
        ``work`` the step's list of the live rows): the live rows are
        updated where they lie; returns ``(mix, leaf', tail', None)``."""
        import jax
        import jax.numpy as jnp
        from ..kernels import mamba2
        c = self._c
        B, T, _ = x.shape
        N, H, P = c["mamba_d_state"], c["mamba_n_heads"], c["mamba_d_head"]
        K, inner = c["mamba_d_conv"], H * P
        conv = inner + 2 * N
        z, xbc, dt = (jnp.dot(x, w("in_proj_" + part),
                              preferred_element_type=jnp.float32)
                      for part in ("z", "xbc", "dt"))
        z = z.astype(x.dtype)
        dt = jax.nn.softplus(dt + w("dt_bias").astype(jnp.float32))
        g = -jnp.exp(w("A_log").astype(jnp.float32)) * dt      # (B, T, H)
        with jax.named_scope("ssm.conv"):
            xbc, seq = causal_conv(xbc.astype(x.dtype), tail, w("conv1d"),
                                   w("conv_bias"))
        xs = xbc[..., :inner].reshape(B, T, H, P)
        Bm, Cm = xbc[..., inner:inner + N], xbc[..., inner + N:]
        new_tail = tail_after(seq, jnp.sum(on, axis=1, dtype=jnp.int32),
                              K - 1)
        snaps = None
        if leaf is not None:
            with jax.named_scope("ssm.step"):
                y, S2 = mamba2.ssd_step_rows(
                    leaf, i, xs[:, 0], dt[:, 0], g[:, 0], Bm[:, 0], Cm[:, 0],
                    w("D"), on[:, 0], work)
            y = y[:, None]
        else:
            with jax.named_scope("ssm.scan"):
                run = functools.partial(mamba2.ssd_prefill,
                                        snapshot_every=every)
                S0 = S.reshape(B, N, H, P)
                if B == 1:      # the engine's prefills: one prompt
                    y, sn, S2 = (a[None] for a in run(
                        xs[0], dt[0], g[0], Bm[0], Cm[0], w("D"), S0[0],
                        on[0]))
                else:
                    y, sn, S2 = jax.vmap(
                        run, in_axes=(0, 0, 0, 0, 0, None, 0, 0))(
                        xs, dt, g, Bm, Cm, w("D"), S0, on)
            S2 = S2.reshape(B, N, inner)
            if every and T >= every:        # the state at each boundary
                snaps = (sn.reshape(B, -1, N, inner), jnp.stack(
                    [seq[:, b:b + K - 1]
                     for b in range(every, T + 1, every)], axis=1))
        y = y.astype(jnp.float32).reshape(B, T, inner) \
            * jax.nn.silu(z.astype(jnp.float32))
        y = rms_norm(y, w("norm"), c["rms_norm_eps"]).astype(x.dtype)
        return jnp.dot(y, w("out_proj"),
                       preferred_element_type=jnp.float32), S2, new_tail, snaps

    def _layer(self, h, w, S, tail, leaf, i, on, every, work=None):
        """One block of the run: ``(h', S' | leaf', tail', snapshots)``."""
        import jax
        c = self._c
        with jax.named_scope("attn.ssm"):
            mix, S2, tail2, snaps = self._mixer(
                rms_norm(h, w("input_layernorm"), c["rms_norm_eps"]), w, S,
                tail, leaf, i, on, every, work)
            h = h + (c["residual_multiplier"] * mix).astype(h.dtype)
        return _mlp(self, h, w), S2, tail2, snaps

    def _block(self, h, positions, carry, live):
        """``carry(update)`` hands ``update`` the rows of state — ``(S (B,
        n, N, H * P), tails (B, n * (K - 1) * conv))``, or for one token a
        row the engine's whole leaves, whose first B rows they are — and
        how often to snapshot, and takes back ``(h', rows',
        snapshots)``."""
        import jax.numpy as jnp
        from jax import lax
        from ..kernels.mamba2 import step_work_list
        del positions                       # the recurrence carries order
        B, T, _ = h.shape
        n = self.ssm_layers
        on = jnp.ones((B, T), bool) if live is None else live
        params = {name: self._w(name) for name in self._names}
        idx = jnp.arange(n, dtype=jnp.int32)
        K1, conv = self._c["mamba_d_conv"] - 1, self._conv
        W = K1 * conv                       # one layer's tail, flat

        def update(rows, every):
            S_all, tails = rows
            if T == 1:      # one token a row, where the rows lie
                # which rows are live is the same for every layer of the
                # run: their list is made once, ahead of the scan
                work = step_work_list(on[:, 0])

                def step(c, xs):
                    hh, leaf, tl = c
                    p, i = xs
                    t0 = lax.dynamic_slice(tl, (0, i * W), (B, W))
                    hh, leaf, t2, _ = self._layer(
                        hh, p.__getitem__, None, t0.reshape(B, K1, conv),
                        leaf, i, on, 0, work)
                    tl = lax.dynamic_update_slice(
                        tl, t2.reshape(B, W).astype(tl.dtype), (0, i * W))
                    return (hh, leaf, tl), None

                (out, S_all, tails), _ = lax.scan(
                    step, (h, S_all, tails), (params, idx))
                return out, (S_all, tails), None

            if S_all.shape[0] != B:
                raise MXNetError(
                    f"{self.name}: {S_all.shape[0]} rows of state for {B} "
                    "sequences: whole leaves are for one token a row")

            def block(hh, xs):
                p, S0, t0 = xs
                hh, S2, t2, snaps = self._layer(
                    hh, p.__getitem__, S0, t0, None, None, on, every)
                return hh, (S2, t2, snaps)

            out, (S2, t2, snaps) = lax.scan(
                block, h, (params, jnp.moveaxis(S_all, 1, 0),
                           jnp.moveaxis(tails.reshape(B, n, K1, conv), 1,
                                        0)))
            if snaps is not None:       # (n, B, boundaries, ...) -> rows
                snaps = (jnp.moveaxis(snaps[0], 0, 2),
                         jnp.moveaxis(snaps[1], 0, 2).reshape(
                             B, -1, n * W))
            return out, (jnp.moveaxis(S2, 0, 1),
                         jnp.moveaxis(t2, 0, 1).reshape(B, n * W)), snaps

        return carry(update), ()

class GraniteAttentionLayer(ServedLayer):
    """One attention block (position-free grouped-query attention, then the
    MLP).  Two KV heads of ``head_dim`` are kept as one of ``2 * head_dim``
    (the module's note on heads of 64)."""

    def __init__(self, cfg, **kwargs):
        self._c = c = cfg
        d, D = c["hidden_size"], c["head_dim"]
        hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
        shapes = _mlp_shapes(c)
        shapes.update(q_proj=(d, hq * D), k_proj=(d, hkv * D),
                      v_proj=(d, hkv * D), o_proj=(hq * D, d))
        #: the softmax scale the engine's attention takes for this layer
        self.attn_scale = float(c["attention_multiplier"])
        super().__init__(shapes, c["dtype"], c["grad_req"], None, **kwargs)

    def _block(self, h, positions, attend, live):
        import jax
        import jax.numpy as jnp
        del positions, live                 # no position signal at all
        c = self._c
        B, T, _ = h.shape
        D, pair = c["head_dim"], c["kv_pair"]
        hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
        x = rms_norm(h, self._w("input_layernorm"), c["rms_norm_eps"])
        q, k, v = (jnp.dot(x, self._w(n), preferred_element_type=jnp.float32
                           ).astype(h.dtype) for n in ("q_proj", "k_proj",
                                                       "v_proj"))
        q = q.reshape(B, T, hq, D)
        if pair > 1:
            # query head n reads KV head n // (hq / hkv), which is half
            # (n // (hq / hkv)) % 2 of the wide head it is stored in
            half = (jnp.arange(hq) // (hq // hkv)) % pair
            mine = (jnp.arange(pair)[None, :] == half[:, None])  # (hq, pair)
            q = jnp.where(mine[None, None, :, :, None], q[..., None, :],
                          jnp.zeros((), q.dtype)).reshape(B, T, hq, pair * D)
        k, v = (a.reshape(B, T, hkv // pair, pair * D) for a in (k, v))
        with jax.named_scope("attn.full"):
            a = attend(q, k, v)                       # (B, T, hq, pair * D)
        if pair > 1:
            a = jnp.sum(jnp.where(mine[None, None, :, :, None],
                                  a.reshape(B, T, hq, pair, D),
                                  jnp.zeros((), a.dtype)), axis=3)
        mix = jnp.dot(a.reshape(B, T, hq * D).astype(h.dtype),
                      self._w("o_proj"), preferred_element_type=jnp.float32)
        h = h + (c["residual_multiplier"] * mix).astype(h.dtype)
        return _mlp(self, h, self._w), ()


class GraniteHybridModel(ServedDecoder):
    """``embedding_multiplier`` x embedding -> the layers of ``layer_types``
    (runs of Mamba-2 blocks, attention blocks) -> RMSNorm -> the
    embedding's own array as the head, ``/ logits_scaling``.  ``grad_req``
    defaults to ``"null"``: the model is served."""

    #: the layers count nothing in the decode programs
    serve_counters = ()

    def __init__(self, vocab_size, hidden_size, num_hidden_layers,
                 layer_types, num_attention_heads, num_key_value_heads,
                 shared_intermediate_size, mamba_n_heads, mamba_d_head,
                 mamba_d_state, mamba_d_conv=4, mamba_n_groups=1,
                 mamba_expand=2, mamba_chunk_size=256, mamba_conv_bias=True,
                 mamba_proj_bias=False, attention_bias=False,
                 attention_multiplier=1.0, embedding_multiplier=1.0,
                 residual_multiplier=1.0, logits_scaling=1.0,
                 num_local_experts=0, position_embedding_type="nope",
                 tie_word_embeddings=True, rms_norm_eps=1e-5,
                 max_position_embeddings=131072, dtype="float32",
                 grad_req="null", **kwargs):
        from ..kernels.mamba2 import CHUNK
        if num_local_experts:
            raise MXNetError(
                f"num_local_experts {num_local_experts}: the model "
                "implemented is the published one, an MLP in every layer")
        if len(layer_types) != num_hidden_layers:
            raise MXNetError(f"{len(layer_types)} layer types for "
                             f"{num_hidden_layers} layers")
        if mamba_n_groups != 1 or not mamba_conv_bias or mamba_proj_bias \
                or attention_bias or position_embedding_type != "nope" \
                or not tie_word_embeddings or mamba_chunk_size != CHUNK \
                or mamba_expand * hidden_size != mamba_n_heads * mamba_d_head:
            raise MXNetError(
                "the model implemented is the published one: B and C "
                "shared by all heads, a bias on the convolution alone, no "
                "position signal, a tied head, chunks of "
                f"{CHUNK}, mamba_expand x hidden_size inner features")
        if num_attention_heads % num_key_value_heads \
                or hidden_size % num_attention_heads:
            raise MXNetError(
                f"{num_attention_heads} query heads on "
                f"{num_key_value_heads} KV heads of a hidden size of "
                f"{hidden_size}: a group is a whole number of heads")
        import jax.numpy as jnp
        head_dim = hidden_size // num_attention_heads
        # two KV heads to a row of 128 lanes where a head is half of one
        pair = 2 if head_dim * 2 == 128 and num_key_value_heads % 2 == 0 \
            else 1
        cfg = dict(
            hidden_size=int(hidden_size), head_dim=int(head_dim),
            num_attention_heads=int(num_attention_heads),
            num_key_value_heads=int(num_key_value_heads), kv_pair=pair,
            shared_intermediate_size=int(shared_intermediate_size),
            mamba_n_heads=int(mamba_n_heads), mamba_d_head=int(mamba_d_head),
            mamba_d_state=int(mamba_d_state), mamba_d_conv=int(mamba_d_conv),
            attention_multiplier=float(attention_multiplier),
            residual_multiplier=float(residual_multiplier),
            rms_norm_eps=float(rms_norm_eps), dtype=jnp.dtype(dtype),
            grad_req=grad_req)
        self.layer_types = tuple(layer_types)
        super().__init__(
            vocab_size, hidden_size, max_position_embeddings, cfg,
            [functools.partial(GraniteMambaRun, cfg, n) if kind == "mamba"
             else functools.partial(GraniteAttentionLayer, cfg)
             for kind, n in layer_runs(layer_types)], grad_req,
            tied_head=True, embed_scale=float(embedding_multiplier),
            logit_divisor=float(logits_scaling), **kwargs)

    def kv_layout(self):
        """The attention layers' keys and values as the pool holds them:
        ``kv_pair`` KV heads to one stored head."""
        out = super().kv_layout()
        pair = self._cfg["kv_pair"]
        out.update(kv_heads=self._cfg["num_key_value_heads"] // pair,
                   head_dim=self._cfg["head_dim"] * pair)
        return out
