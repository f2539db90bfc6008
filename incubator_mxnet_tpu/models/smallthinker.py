"""SmallThinker decoder (``PowerInfer/SmallThinker-21BA3B-Instruct``; the
published description is its config.json keys and modelling code): pre-norm
blocks, grouped-query attention with window-and-rotary layers and full
position-free layers mixed, and in every layer softmax-routed dropless
ReGLU experts whose router reads the LAYER'S INPUT — before the attention
norm — so that a deployment can fetch the chosen experts' weights while
attention runs.  No shared expert, no QK-norm, no output gate, no bias, an
untied head.

For layer ``i`` with input ``h``::

    r   = h W_r                                  # float32, from the input
    x   = RMSNorm(h);  q, k, v = x W_q, x W_k, x W_v
    if rope_layout[i]:  q, k = RoPE(q, k)
    h1  = h + Attn(q, k, v; window if sliding_window_layout[i]) W_o
    y   = RMSNorm(h1)
    idx = top_k(r);  w = softmax(r[idx])
    out = h1 + sum_k w_k W_down[idx_k](relu(y W_gate[idx_k]) * y W_up[idx_k])

The router's product is issued first in program order, under the device
scope ``moe.route``; its result is used only after attention
(``moe.experts``).  This file starts no prefetch.  The neuron-level
sparsity predictor of the paper is an inference device, not part of the
function, and is not here.

Constructor arguments are the source's keys.  What a chip of an
expert-parallel group holds is said as ``AFMoEModel`` says it:
``moe_num_primary_experts`` experts HELD of
``moe_num_primary_experts_published`` (the router keeps its published
width), ``first_expert`` the first of them.  The shared part of a served
decoder is ``models/decoder.py``.
"""
from __future__ import annotations

import functools

from ..base import MXNetError
from .decoder import ServedDecoder, ServedLayer, rms_norm, rotary
from .moe import held_experts_ffn, route_token_choice

__all__ = ["SmallThinkerLayer", "SmallThinkerModel"]


class SmallThinkerLayer(ServedLayer):
    """One pre-norm block, router first.  ``rope``: q and k carry the
    rotary embedding; ``sliding``: keys ``j`` with ``p - window < j <= p``
    only."""

    def __init__(self, cfg, rope, sliding, **kwargs):
        self._c = c = cfg
        self._rope = bool(rope)
        d, D = c["hidden_size"], c["head_dim"]
        hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
        f, E = c["moe_ffn_hidden_size"], c["num_experts"]
        super().__init__({
            "router": (d, c["num_experts_published"]),
            "input_layernorm": (d,), "q_proj": (d, hq * D),
            "k_proj": (d, hkv * D), "v_proj": (d, hkv * D),
            "o_proj": (hq * D, d), "post_attention_layernorm": (d,),
            "experts_gate": (E, d, f), "experts_up": (E, d, f),
            "experts_down": (E, f, d),
        }, c["dtype"], c["grad_req"],
            c["sliding_window_size"] if sliding else None, **kwargs)

    def _block(self, h, positions, attend, live):
        import jax
        import jax.numpy as jnp
        c = self._c
        B, T, d = h.shape
        D, eps = c["head_dim"], c["rms_norm_eps"]
        with jax.named_scope("moe.route"):
            # from the layer's input, in float32, over all published experts
            logits = jnp.dot(h.reshape(B * T, d).astype(jnp.float32),
                             self._w("router").astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            idx, w = route_token_choice(
                logits, None, c["num_experts_per_tok"], score="softmax")
        x = rms_norm(h, self._w("input_layernorm"), eps)
        q, k, v = (jnp.dot(x, self._w(n), preferred_element_type=jnp.float32
                           ).astype(h.dtype).reshape(B, T, -1, D)
                   for n in ("q_proj", "k_proj", "v_proj"))
        if self._rope:              # the other layers carry no position
            q = rotary(q, positions, c["rope_theta"])
            k = rotary(k, positions, c["rope_theta"])
        with jax.named_scope("attn.full" if self.window is None
                             else "attn.window"):
            a = attend(q, k, v)                       # (B, T, Hq, D)
        h = h + jnp.dot(a.reshape(B, T, -1), self._w("o_proj"),
                        preferred_element_type=jnp.float32).astype(h.dtype)
        y = rms_norm(h, self._w("post_attention_layernorm"), eps)
        with jax.named_scope("moe.experts"):
            m, counts = held_experts_ffn(
                y.reshape(B * T, d), idx, w,
                (c["first_expert"], c["num_experts"]),
                self._w("experts_gate"), self._w("experts_up"),
                self._w("experts_down"),
                None if live is None else live.reshape(B * T), act="relu")
        return h + m.astype(h.dtype).reshape(B, T, d), counts


class SmallThinkerModel(ServedDecoder):
    """Embedding (unscaled) -> ``num_hidden_layers`` layers -> final
    RMSNorm -> untied head without bias.  ``rope_layout`` and
    ``sliding_window_layout`` have one 0/1 entry a layer.
    ``moe_num_primary_experts`` is what this chip holds,
    ``moe_num_primary_experts_published`` (default: the same) what the
    router scores, ``first_expert`` where the held range starts.
    ``grad_req`` defaults to ``"null"``: the model is served, and a
    gradient buffer per weight would double 8 GB."""

    def __init__(self, vocab_size, hidden_size, num_hidden_layers,
                 num_attention_heads, num_key_value_heads, head_dim,
                 moe_num_primary_experts, moe_num_active_primary_experts,
                 moe_ffn_hidden_size, rope_layout, sliding_window_layout,
                 sliding_window_size=4096,
                 moe_num_primary_experts_published=None, first_expert=0,
                 moe_primary_router_apply_softmax=True, norm_topk_prob=True,
                 rope_theta=1.5e6, rms_norm_eps=1e-6,
                 max_position_embeddings=16384, dtype="float32",
                 grad_req="null", **kwargs):
        rope_layout, sliding = list(rope_layout), list(sliding_window_layout)
        if not len(rope_layout) == len(sliding) == num_hidden_layers:
            raise MXNetError(
                f"rope_layout names {len(rope_layout)} layers, "
                f"sliding_window_layout {len(sliding)}, num_hidden_layers "
                f"is {num_hidden_layers}")
        if num_attention_heads % num_key_value_heads:
            raise MXNetError(
                f"{num_attention_heads} query heads do not divide over "
                f"{num_key_value_heads} KV heads")
        if not (moe_primary_router_apply_softmax and norm_topk_prob):
            raise MXNetError(
                "the router implemented is the published one: softmax "
                "scores, the chosen ones renormalised")
        held = int(moe_num_primary_experts)
        published = int(moe_num_primary_experts_published or held)
        if not 0 <= first_expert <= published - held:
            raise MXNetError(
                f"experts {first_expert}..{first_expert + held - 1} are "
                f"not among the {published} published")
        import jax.numpy as jnp
        cfg = dict(
            hidden_size=int(hidden_size), head_dim=int(head_dim),
            num_attention_heads=int(num_attention_heads),
            num_key_value_heads=int(num_key_value_heads),
            moe_ffn_hidden_size=int(moe_ffn_hidden_size),
            num_experts=held, num_experts_published=published,
            first_expert=int(first_expert),
            num_experts_per_tok=int(moe_num_active_primary_experts),
            sliding_window_size=int(sliding_window_size),
            rope_theta=float(rope_theta), rms_norm_eps=float(rms_norm_eps),
            dtype=jnp.dtype(dtype), grad_req=grad_req)
        super().__init__(
            vocab_size, hidden_size, max_position_embeddings, cfg,
            [functools.partial(SmallThinkerLayer, cfg, bool(r), bool(s))
             for r, s in zip(rope_layout, sliding)], grad_req, **kwargs)
