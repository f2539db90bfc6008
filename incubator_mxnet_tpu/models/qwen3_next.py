"""Qwen3-Next decoder (``model_type: qwen3_next``; the published description
is the ``Qwen/Qwen3-Next-80B-A3B-Instruct`` config.json keys and modelling
code): pre-norm blocks whose sequence mixing is a Gated DeltaNet in three
layers of four — a float32 matrix a value head, updated every token, and a
short causal convolution's tail: state of a constant size, no keys — and
gated softmax attention on heads of 256 in the fourth; in every layer
softmax-routed dropless SwiGLU experts beside a shared expert under a
sigmoid gate; an untied head.  Every RMSNorm but the one inside the
DeltaNet is zero-centred: ``x_hat * (1 + w)``.

Layer ``l`` is full attention where ``(l + 1) % full_attention_interval ==
0``.  For input ``h``::

    h  = h + Mix(norm1(h));   h = h + MoE(norm2(h))

    Gated DeltaNet:
    [q, k, v, z] = x W_qkvz;   [b, a] = x W_ba
    (q, k, v) <- silu(causal depthwise conv, width 4, no bias)
    q, k <- l2norm per head (eps 1e-6);  q <- q * Dk^-1/2;  a key head serves
    ``linear_num_value_heads / linear_num_key_heads`` value heads
    beta = sigmoid(b);   g = -exp(A_log) * softplus(a + dt_bias)
    S <- e^g S;  u = beta (v - S^T k);  S <- S + k u^T;  o = S^T q
    Mix = (rmsnorm(o; w) * silu(z)) W_o          # this norm's weight is plain

    full attention:
    [q | gate] = x W_q;  k, v = x W_k, x W_v;  q, k <- zero-centred RMSNorm
    per head;  rotary (half-rotation) on the first ``partial_rotary_factor``
    of a head's features;  Mix = (softmax(q k^T / sqrt(D)) v * sigmoid(gate)) W_o

    MoE = sum_k p_k SwiGLU_{e_k}(y) over the experts held
          + sigmoid(y . w_s) SwiGLU_shared(y),   p = softmax(y W_r) top-k renormalised

Constructor arguments are the source's keys.  What a chip of a group that
shares a layer holds is said as ``AFMoEModel`` says it: ``num_experts``
experts HELD of ``num_experts_published`` from ``first_expert`` on, and its
share of the query, KV, key and value heads and of the vocabulary as plain
smaller counts.  The column order inside ``W_qkvz``, ``W_ba`` and ``W_q`` is
this file's own (``[q | k | v | z]``, ``[b | a]``, ``(head, [q, gate], D)``):
with seeded weights it names the same distribution as the source's.  The
multi-token-prediction module is not part of the next-token function and is
not here.

The serving seam (``docs/serving.md`` "The layer interface"): a full layer
is handed ``attend`` and keeps blocks; a DeltaNet layer states
``state_shapes`` and is handed the engine's hand for its state rows
(``ServedLayer.serve_recurrent``).
"""
from __future__ import annotations

import functools

from ..base import MXNetError
from .decoder import (ServedDecoder, ServedLayer, causal_conv, rms_norm,
                      rotary, tail_after)
from .moe import _glu, held_experts_ffn, route_token_choice

__all__ = ["Qwen3NextLayer", "Qwen3NextModel", "causal_conv"]

#: tokens the expert layer takes at a time (a longer prompt in pieces)
_EXPERT_ROWS = 8192


class Qwen3NextLayer(ServedLayer):
    """One pre-norm block: ``linear`` layers mix with the Gated DeltaNet,
    the others with gated attention; both end in the experts."""

    def __init__(self, cfg, linear, **kwargs):
        self._c = c = cfg
        self._linear = bool(linear)
        d = c["hidden_size"]
        f, E = c["moe_intermediate_size"], c["num_experts"]
        fs = c["shared_expert_intermediate_size"]
        shapes = {"input_layernorm": (d,), "post_attention_layernorm": (d,)}
        if self._linear:
            hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
            dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
            conv = 2 * hk * dk + hv * dv
            shapes.update(
                in_proj_qkvz=(d, conv + hv * dv), in_proj_ba=(d, 2 * hv),
                conv1d=(c["linear_conv_kernel_dim"], conv), dt_bias=(hv,),
                A_log=(hv,), norm=(dv,), out_proj=(hv * dv, d))
            #: what a sequence keeps of this layer: the matrices and the
            #: convolution's tail, float32 both
            self.state_shapes = (
                ((hv, dk, dv), "float32"),
                ((c["linear_conv_kernel_dim"] - 1, conv), "float32"))
        else:
            D = c["head_dim"]
            hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
            shapes.update(
                q_proj=(d, hq * 2 * D), k_proj=(d, hkv * D),
                v_proj=(d, hkv * D), q_norm=(D,), k_norm=(D,),
                o_proj=(hq * D, d))
        shapes.update(
            router=(d, c["num_experts_published"]),
            experts_gate=(E, d, f), experts_up=(E, d, f),
            experts_down=(E, f, d), shared_gate=(d, fs), shared_up=(d, fs),
            shared_down=(fs, d), shared_expert_gate=(d, 1))
        super().__init__(shapes, c["dtype"], c["grad_req"], None,
                         random=("dt_bias", "A_log"), **kwargs)

    def _norm(self, x, name):
        """The zero-centred RMSNorm: ``x_hat * (1 + w)``."""
        import jax.numpy as jnp
        return rms_norm(x, 1.0 + self._w(name).astype(jnp.float32),
                        self._c["rms_norm_eps"])

    def _proj(self, x, name):
        import jax.numpy as jnp
        return jnp.dot(x, self._w(name), preferred_element_type=jnp.float32)

    # -- the two mixers ---------------------------------------------------
    def _delta_net(self, x, carry, live):
        """x (B, T, d) normed; ``carry(update)`` hands ``update`` the rows
        of state ``(S (B, Hv, Dk, Dv), tail (B, K - 1, conv))`` and how often
        to snapshot, and takes back ``(out, rows', snapshots)``."""
        import jax
        import jax.numpy as jnp
        from ..kernels.gated_delta import gated_delta_prefill, \
            gated_delta_step
        c = self._c
        B, T, _ = x.shape
        hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
        dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
        K = c["linear_conv_kernel_dim"]
        conv = 2 * hk * dk + hv * dv
        qkvz = self._proj(x, "in_proj_qkvz").astype(x.dtype)
        ba = self._proj(x, "in_proj_ba")                       # float32
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(self._w("A_log").astype(jnp.float32)) * jax.nn.softplus(
            ba[..., hv:] + self._w("dt_bias").astype(jnp.float32))
        z = qkvz[..., conv:].reshape(B, T, hv, dv)
        on = jnp.ones((B, T), bool) if live is None else live
        n_live = jnp.sum(on, axis=1, dtype=jnp.int32)

        def update(rows, every):
            S, tail = rows
            mixed, seq = causal_conv(qkvz[..., :conv], tail,
                                     self._w("conv1d"))
            q, k = (mixed[..., i * hk * dk:(i + 1) * hk * dk].astype(
                jnp.float32).reshape(B, T, hk, dk) for i in (0, 1))
            q, k = (a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True)
                                      + 1e-6) for a in (q, k))
            q, k = (jnp.repeat(a, hv // hk, axis=2)
                    for a in (q * dk ** -0.5, k))
            v = mixed[..., 2 * hk * dk:].reshape(B, T, hv, dv)
            snaps = None
            if T == 1:
                o, S2 = gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                         beta[:, 0], S, on)
                o = o[:, None]
            else:
                run = functools.partial(gated_delta_prefill,
                                        snapshot_every=every)
                if B == 1:      # the engine's prefills: one prompt
                    o, sn, S2 = (a[None] for a in run(
                        q[0], k[0], v[0], g[0], beta[0], S[0], on[0]))
                else:
                    o, sn, S2 = jax.vmap(run)(q, k, v, g, beta, S, on)
                if every and T >= every:    # the state at each boundary
                    snaps = (sn, jnp.stack(
                        [seq[:, b:b + K - 1]
                         for b in range(every, T + 1, every)], axis=1))
            return o, (S2, tail_after(seq, n_live, K - 1)), snaps

        o = carry(update)                              # (B, T, Hv, Dv) f32
        y = rms_norm(o, self._w("norm"), c["rms_norm_eps"]) \
            * jax.nn.silu(z.astype(jnp.float32))
        return self._proj(y.astype(x.dtype).reshape(B, T, hv * dv),
                          "out_proj").astype(x.dtype)

    def _attention(self, x, positions, attend):
        import jax
        import jax.numpy as jnp
        c = self._c
        B, T, _ = x.shape
        D = c["head_dim"]
        qg = self._proj(x, "q_proj").astype(x.dtype).reshape(B, T, -1, 2, D)
        q, gate = qg[..., 0, :], qg[..., 1, :]
        k, v = (self._proj(x, n).astype(x.dtype).reshape(B, T, -1, D)
                for n in ("k_proj", "v_proj"))
        turned = int(D * c["partial_rotary_factor"])
        q = rotary(self._norm(q, "q_norm"), positions, c["rope_theta"],
                   turned)
        k = rotary(self._norm(k, "k_norm"), positions, c["rope_theta"],
                   turned)
        a = attend(q, k, v)                               # (B, T, Hq, D)
        a = (a.astype(jnp.float32)
             * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(x.dtype)
        return self._proj(a.reshape(B, T, -1), "o_proj").astype(x.dtype)

    def _experts(self, y, live):
        """The expert layer over y (B, T, d), ``_EXPERT_ROWS`` tokens at a
        time: the grouped product sorts T x k (token, expert) rows and
        keeps them in float32, which for a prompt of 16,384 tokens is
        gigabytes beside 9 GB of weights and state."""
        import jax.numpy as jnp
        from jax import lax
        B, T, d = y.shape
        n = B * T
        yt = y.reshape(n, d)
        on = jnp.ones(n, bool) if live is None else live.reshape(n)
        if n <= _EXPERT_ROWS or n % _EXPERT_ROWS:
            m, counts = self._experts_rows(yt, on)
        else:
            m, counts = lax.map(
                lambda a: self._experts_rows(*a),
                (yt.reshape(-1, _EXPERT_ROWS, d),
                 on.reshape(-1, _EXPERT_ROWS)))
            m, counts = m.reshape(n, d), tuple(jnp.sum(c) for c in counts)
        return m.reshape(B, T, d), counts

    def _experts_rows(self, yt, live):
        """yt (n, d), live (n,) -> ``(MoE(yt) in yt's type, counts)``."""
        import jax
        import jax.numpy as jnp
        c = self._c
        with jax.named_scope("moe.route"):
            logits = jnp.dot(yt.astype(jnp.float32),
                             self._w("router").astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            idx, w = route_token_choice(
                logits, None, c["num_experts_per_tok"], score="softmax")
        with jax.named_scope("moe.experts"):
            m, counts = held_experts_ffn(
                yt, idx, w, (c["first_expert"], c["num_experts"]),
                self._w("experts_gate"), self._w("experts_up"),
                self._w("experts_down"), live)
        with jax.named_scope("moe.shared"):
            gate = jax.nn.sigmoid(jnp.dot(
                yt, self._w("shared_expert_gate"),
                preferred_element_type=jnp.float32))          # (n, 1)
            m = m + gate * _glu(yt, self._w("shared_gate"),
                                self._w("shared_up"), self._w("shared_down"))
        return m.astype(yt.dtype), counts

    def _block(self, h, positions, attend, live):
        import jax
        x = self._norm(h, "input_layernorm")
        if self._linear:
            with jax.named_scope("attn.linear"):
                h = h + self._delta_net(x, attend, live)
        else:
            with jax.named_scope("attn.full"):
                h = h + self._attention(x, positions, attend)
        m, counts = self._experts(
            self._norm(h, "post_attention_layernorm"), live)
        return h + m, counts


class Qwen3NextModel(ServedDecoder):
    """Embedding -> ``num_hidden_layers`` layers -> zero-centred final
    RMSNorm -> untied head without bias.  ``num_experts`` is what this chip
    holds, ``num_experts_published`` (default: the same) what the router
    scores, ``first_expert`` where the held range starts; the head counts
    and ``vocab_size`` are this chip's share.  ``grad_req`` defaults to
    ``"null"``: the model is served."""

    def __init__(self, vocab_size, hidden_size, num_hidden_layers,
                 num_attention_heads, num_key_value_heads, head_dim,
                 linear_num_key_heads, linear_num_value_heads,
                 linear_key_head_dim, linear_value_head_dim,
                 moe_intermediate_size, shared_expert_intermediate_size,
                 num_experts, num_experts_per_tok,
                 full_attention_interval=4, linear_conv_kernel_dim=4,
                 partial_rotary_factor=0.25, norm_topk_prob=True,
                 num_experts_published=None, first_expert=0,
                 decoder_sparse_step=1, mlp_only_layers=(),
                 rope_theta=1e7, rms_norm_eps=1e-6,
                 max_position_embeddings=262144, dtype="float32",
                 grad_req="null", **kwargs):
        if num_attention_heads % num_key_value_heads \
                or linear_num_value_heads % linear_num_key_heads:
            raise MXNetError(
                f"{num_attention_heads} query heads on "
                f"{num_key_value_heads} KV heads, {linear_num_value_heads} "
                f"value heads on {linear_num_key_heads} key heads: a group "
                "is a whole number of heads")
        if not norm_topk_prob or decoder_sparse_step != 1 \
                or list(mlp_only_layers):
            raise MXNetError(
                "the model implemented is the published one: experts in "
                "every layer, the chosen scores renormalised")
        published = int(num_experts_published or num_experts)
        if not 0 <= first_expert <= published - num_experts:
            raise MXNetError(
                f"experts {first_expert}..{first_expert + num_experts - 1} "
                f"are not among the {published} published")
        import jax.numpy as jnp
        cfg = dict(
            hidden_size=int(hidden_size), head_dim=int(head_dim),
            num_attention_heads=int(num_attention_heads),
            num_key_value_heads=int(num_key_value_heads),
            linear_num_key_heads=int(linear_num_key_heads),
            linear_num_value_heads=int(linear_num_value_heads),
            linear_key_head_dim=int(linear_key_head_dim),
            linear_value_head_dim=int(linear_value_head_dim),
            linear_conv_kernel_dim=int(linear_conv_kernel_dim),
            partial_rotary_factor=float(partial_rotary_factor),
            moe_intermediate_size=int(moe_intermediate_size),
            shared_expert_intermediate_size=int(
                shared_expert_intermediate_size),
            num_experts=int(num_experts), num_experts_published=published,
            first_expert=int(first_expert),
            num_experts_per_tok=int(num_experts_per_tok),
            rope_theta=float(rope_theta), rms_norm_eps=float(rms_norm_eps),
            dtype=jnp.dtype(dtype), grad_req=grad_req)
        every = int(full_attention_interval)
        super().__init__(
            vocab_size, hidden_size, max_position_embeddings, cfg,
            [functools.partial(Qwen3NextLayer, cfg, (i + 1) % every != 0)
             for i in range(num_hidden_layers)], grad_req, **kwargs)

    def serve_head(self, h):
        """h (B, T, d) -> float32 logits (B, T, vocab); the final norm is
        zero-centred like the layers'."""
        import jax.numpy as jnp
        return self._head_logits(rms_norm(
            h, 1.0 + self.norm.data()._data.astype(jnp.float32),
            self._cfg["rms_norm_eps"]))
