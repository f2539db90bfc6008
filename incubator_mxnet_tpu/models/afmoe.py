"""AFMoE decoder (``model_type: afmoe``; the published description is the
``arcee-ai/Trinity-*`` config.json keys and modelling code): RMSNorm
sandwich blocks, gated grouped-query attention with window and
position-free layers mixed, SwiGLU dense layers first and then
sigmoid-routed dropless experts beside a shared expert, an untied head.

Constructor arguments are the source's keys.  What a chip of an
expert-parallel group holds is said with the same keys: ``num_experts``
experts HELD of ``num_experts_published`` (the router keeps its published
width), ``first_expert`` the first of them, and the chip's share of the
heads and of the vocabulary as plain smaller counts.  Nothing here stands
in for the absent chips: a share computes its own experts' part of the sum
and that partial result goes on.

Every matrix is stored ``(in, out)`` and applied as ``x @ W`` (the
source's ``nn.Linear`` stores ``(out, in)``); matrices and activations are
of ``dtype`` (bfloat16 as served), products accumulate in float32, and the
norms, the rotary embedding, the router's scores and the softmax are
computed in float32.

The serving seam (``docs/serving.md`` "The layer interface"):
``kv_layout``, ``serve_embed``, ``serve_layers``, ``serve_head`` on the
model, ``serve_prefill`` / ``serve_cached`` on a layer — the same methods
``GPTModel`` / ``GPTCell`` implement, and all ``GenerationEngine``'s paged
programs call.
"""
from __future__ import annotations

import functools
import math

from ..base import MXNetError
from .decoder import ServedDecoder, ServedLayer, rms_norm, rotary
from .moe import _glu, held_experts_ffn, route_token_choice

__all__ = ["AFMoELayer", "AFMoEModel", "swiglu_ffn", "rms_norm",
           "rotary"]


def swiglu_ffn(layer, x, live):
    """The FFN of a ``layer`` that names its parameters as
    :class:`AFMoELayer` does, over x (B, T, d): ``(y, counts)``.  A
    ``_dense`` layer is one SwiGLU (``mlp_*``; counts is ``()``); any other
    routes by sigmoid scores with a choice-only bias
    (``moe.route_token_choice``), computes the part of the sum that the
    experts HELD here give (``moe.held_experts_ffn``) and adds the shared
    expert whole; counts are the three of ``MOE_COUNTERS``.  ``layer._c``
    gives ``num_experts_per_tok``, ``route_norm``, ``route_scale``,
    ``first_expert`` and ``num_experts``."""
    import jax
    import jax.numpy as jnp
    c, w = layer._c, layer._w
    B, T, d = x.shape
    xt = x.reshape(B * T, d)
    if layer._dense:
        y = _glu(xt, w("mlp_gate"), w("mlp_up"), w("mlp_down"))
        return y.astype(x.dtype).reshape(B, T, d), ()
    with jax.named_scope("moe.route"):
        # the router's scores in float32, over all published experts
        logits = jnp.dot(xt.astype(jnp.float32),
                         w("router").astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        idx, wt = route_token_choice(
            logits, w("expert_bias"), c["num_experts_per_tok"],
            c["route_norm"], c["route_scale"])
    with jax.named_scope("moe.experts"):
        y, counts = held_experts_ffn(
            xt, idx, wt, (c["first_expert"], c["num_experts"]),
            w("experts_gate"), w("experts_up"), w("experts_down"),
            None if live is None else live.reshape(B * T))
    with jax.named_scope("moe.shared"):
        y = y + _glu(xt, w("shared_gate"), w("shared_up"),
                     w("shared_down"))
    return y.astype(x.dtype).reshape(B, T, d), counts


class AFMoELayer(ServedLayer):
    """One sandwich block: ``h += post_attn_norm(Attn(input_norm(h)))``,
    ``h += post_mlp_norm(FFN(pre_mlp_norm(h)))``.  ``sliding`` layers
    carry the rotary embedding and a causal window; full layers carry no
    position at all.  ``dense`` layers have one SwiGLU of
    ``intermediate_size``; the others the routed experts held here plus
    the shared expert."""

    def __init__(self, cfg, sliding, dense, **kwargs):
        self._c = c = cfg
        self._sliding = bool(sliding)
        self._dense = bool(dense)
        d, D = c["hidden_size"], c["head_dim"]
        hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
        shapes = {
            "input_layernorm": (d,), "post_attention_layernorm": (d,),
            "pre_mlp_layernorm": (d,), "post_mlp_layernorm": (d,),
            "q_proj": (d, hq * D), "k_proj": (d, hkv * D),
            "v_proj": (d, hkv * D), "gate_proj": (d, hq * D),
            "o_proj": (hq * D, d), "q_norm": (D,), "k_norm": (D,),
        }
        if self._dense:
            f = c["intermediate_size"]
            shapes.update(mlp_gate=(d, f), mlp_up=(d, f), mlp_down=(f, d))
        else:
            f, E = c["moe_intermediate_size"], c["num_experts"]
            fs = f * c["num_shared_experts"]
            shapes.update(
                router=(d, c["num_experts_published"]),
                expert_bias=(c["num_experts_published"],),
                experts_gate=(E, d, f), experts_up=(E, d, f),
                experts_down=(E, f, d),
                shared_gate=(d, fs), shared_up=(d, fs), shared_down=(fs, d))
        super().__init__(
            shapes, c["dtype"], c["grad_req"],
            c["sliding_window"] if self._sliding else None,
            random=("expert_bias",), **kwargs)

    # -- the block, over jax arrays -------------------------------------
    def _attention_inputs(self, h, positions):
        import jax.numpy as jnp
        c = self._c
        B, T, _ = h.shape
        D, eps = c["head_dim"], c["rms_norm_eps"]
        x = rms_norm(h, self._w("input_layernorm"), eps)

        def proj(name):
            y = jnp.dot(x, self._w(name), preferred_element_type=jnp.float32)
            return y.astype(h.dtype).reshape(B, T, -1, D)

        q, k, v, g = (proj(n) for n in ("q_proj", "k_proj", "v_proj",
                                        "gate_proj"))
        q = rms_norm(q, self._w("q_norm"), eps)
        k = rms_norm(k, self._w("k_norm"), eps)
        if self._sliding:           # full layers carry no position
            q = rotary(q, positions, c["rope_theta"])
            k = rotary(k, positions, c["rope_theta"])
        return q, k, v, g

    def _block(self, h, positions, attend, live):
        import jax
        import jax.numpy as jnp
        c = self._c
        eps = c["rms_norm_eps"]
        B, T, _ = h.shape
        q, k, v, g = self._attention_inputs(h, positions)
        with jax.named_scope("attn.window" if self._sliding
                             else "attn.full"):
            a = attend(q, k, v)                       # (B, T, Hq, D)
        a = (a.astype(jnp.float32)
             * jax.nn.sigmoid(g.astype(jnp.float32))).astype(h.dtype)
        o = jnp.dot(a.reshape(B, T, -1), self._w("o_proj"),
                    preferred_element_type=jnp.float32).astype(h.dtype)
        h = h + rms_norm(o, self._w("post_attention_layernorm"), eps)
        m, counts = swiglu_ffn(
            self, rms_norm(h, self._w("pre_mlp_layernorm"), eps), live)
        return h + rms_norm(m, self._w("post_mlp_layernorm"), eps), counts


class AFMoEModel(ServedDecoder):
    """Embedding (times ``sqrt(hidden_size)`` when ``mup_enabled``) ->
    layers -> final RMSNorm -> untied head without bias.

    ``num_hidden_layers`` layers of ``layer_types`` (one entry a layer:
    ``"sliding_attention"`` or ``"full_attention"``), the first
    ``num_dense_layers`` of them dense.  ``num_experts`` is what this
    chip holds, ``num_experts_published`` (default: the same) what the
    router scores, ``first_expert`` where the held range starts.
    ``grad_req`` defaults to ``"null"``: this PR serves the model, and a
    gradient buffer per weight would double 8 GB."""

    def __init__(self, vocab_size, hidden_size, intermediate_size,
                 moe_intermediate_size, num_hidden_layers,
                 num_dense_layers, layer_types, num_attention_heads,
                 num_key_value_heads, head_dim, num_experts,
                 num_experts_per_tok, num_shared_experts=1,
                 num_experts_published=None, first_expert=0,
                 sliding_window=4096, rope_theta=10000.0,
                 rms_norm_eps=1e-5, route_norm=True, route_scale=1.0,
                 mup_enabled=True, max_position_embeddings=262144,
                 dtype="float32", grad_req="null", **kwargs):
        layer_types = list(layer_types)
        if len(layer_types) != num_hidden_layers:
            raise MXNetError(
                f"layer_types names {len(layer_types)} layers, "
                f"num_hidden_layers is {num_hidden_layers}")
        if num_attention_heads % num_key_value_heads:
            raise MXNetError(
                f"{num_attention_heads} query heads do not divide over "
                f"{num_key_value_heads} KV heads")
        published = int(num_experts_published or num_experts)
        if not 0 <= first_expert <= published - num_experts:
            raise MXNetError(
                f"experts {first_expert}..{first_expert + num_experts - 1} "
                f"are not among the {published} published")
        import jax.numpy as jnp
        cfg = dict(
            hidden_size=int(hidden_size), head_dim=int(head_dim),
            intermediate_size=int(intermediate_size),
            moe_intermediate_size=int(moe_intermediate_size),
            num_attention_heads=int(num_attention_heads),
            num_key_value_heads=int(num_key_value_heads),
            num_experts=int(num_experts), num_experts_published=published,
            first_expert=int(first_expert),
            num_experts_per_tok=int(num_experts_per_tok),
            num_shared_experts=int(num_shared_experts),
            sliding_window=int(sliding_window),
            rope_theta=float(rope_theta), rms_norm_eps=float(rms_norm_eps),
            route_norm=bool(route_norm), route_scale=float(route_scale),
            dtype=jnp.dtype(dtype), grad_req=grad_req)
        self._mup = bool(mup_enabled)
        for i, kind in enumerate(layer_types):
            if kind not in ("sliding_attention", "full_attention"):
                raise MXNetError(f"layer {i}: no such layer type {kind!r}")
        super().__init__(
            vocab_size, hidden_size, max_position_embeddings, cfg,
            [functools.partial(AFMoELayer, cfg, kind == "sliding_attention",
                               i < num_dense_layers)
             for i, kind in enumerate(layer_types)], grad_req, **kwargs)

    def serve_embed(self, tokens, positions):
        """tokens, positions (B, T) int32 -> h (B, T, d), times
        ``sqrt(hidden_size)`` when ``mup_enabled``."""
        h = super().serve_embed(tokens, positions)
        if self._mup:
            h = (h.astype("float32") * math.sqrt(self._units)
                 ).astype(h.dtype)
        return h
