"""dots3-note decoder (``model_type: dots3_note``; the published description
is the ``dots-studio/dots3-note-prev`` config.json keys, each a mechanism
with a published form): pre-norm blocks of LATENT attention — queries and
key-values through low-rank projections, ONE row ``[c | k_r]`` a cached
position for all heads (DeepSeek-V2/V3's multi-head latent attention) — in
two kinds mixed by ``layer_types``:

* ``full_attention``: ``num_attention_heads`` heads over a latent of
  ``kv_lora_rank``, and the lightning indexer of DeepSeek-V3.2-Exp beside
  it — ``index_n_heads`` narrow heads score every cached position's index
  key and the layer attends over exactly the ``index_topk`` of largest
  score;
* ``sliding_attention``: ``swa_num_attention_heads`` heads over a latent
  of ``swa_kv_lora_rank``, the latest ``sliding_window_size`` positions.

Both gate each head's output by one sigmoid scalar (``headwise``).  Layer 0
is a dense SwiGLU; the others route by sigmoid scores with a choice-only
bias (``noaux_tc``, no groups) to ``num_experts_per_tok`` of the published
experts beside one shared expert — ``models/afmoe.py``'s expert layer,
called as it calls it.

For a layer of input ``h`` (``x = RMSNorm(h)``, ``s_q = sqrt(d / r_q)``,
``s_kv = sqrt(d / r_kv)`` where ``apply_mla_qkv_lora_rescale``)::

    c_q           = s_q RMSNorm(x W_qa)
    [q_n | q_r]_h = c_q W_qb        (per head);  q_r = RoPE(q_r)
    [c | k_r]     = x W_kva;  c = s_kv RMSNorm(c);  k_r = RoPE(k_r)
    k_n,h = c W_uk,h;  v_h = c W_uv,h           (W_kvb = [W_uk | W_uv]_h)
    a_h(t, s) = (q_n,h(t) . k_n,h(s) + q_r,h(t) . k_r(s)) / sqrt(d_n + d_r)
    o_h = sigmoid(x W_g)_h  sum_s softmax_s(a_h)(t, s) v_h(s)
    h1  = h + concat_h(o_h) W_o;   out = h1 + FFN(RMSNorm(h1))

and in a full layer the allowed ``s`` are the ``min(k, t + 1)`` positions
of largest ``I(t, s) = sum_j w_j(t) relu(q_I,j(t) . k_I(s))`` with ``q_I =
RoPE64(c_q W_Iq)``, ``k_I = RoPE64(LayerNorm(x W_Ik))``, ``w = x W_Iw /
sqrt(H_I d_I)``.  The cache keeps ``[c | k_r]`` and, in a full layer,
``k_I`` (``kv_rows``); how a prompt (unabsorbed) and a decode step
(absorbed) read it is ``kernels/latent_attention.py``'s.

Constructor arguments are the source's keys.  A chip's share is said as
``AFMoEModel`` says it: ``n_routed_experts`` HELD of
``n_routed_experts_published`` from ``first_expert``, and its share of the
heads of either kind and of the vocabulary as plain smaller counts; the
indexer is whole on every chip (every chip scores every key).  Every
matrix is stored ``(in, out)``; the shared part of a served decoder is
``models/decoder.py``.
"""
from __future__ import annotations

import functools
import math

from ..base import MXNetError
from .afmoe import swiglu_ffn
from .decoder import ServedDecoder, ServedLayer, rms_norm, rotary

__all__ = ["Dots3Layer", "Dots3Model"]

#: the LayerNorm of the index key (its own small epsilon, as published)
_INDEX_NORM_EPS = 1e-6


class Dots3Layer(ServedLayer):
    """One pre-norm block.  ``sliding``: the window kind (the ``swa_*``
    sizes, no indexer); ``dense``: one SwiGLU of ``intermediate_size``
    instead of the experts."""

    def __init__(self, cfg, sliding, dense, **kwargs):
        self._c = c = cfg
        self._sliding = bool(sliding)
        self._dense = bool(dense)
        a = self._a = c["sliding"] if sliding else c["full"]
        d = c["hidden_size"]
        H, r_q, r = a["heads"], a["q_rank"], a["kv_rank"]
        d_n, d_r, d_v = a["nope"], a["rope"], a["v"]
        shapes = {
            "input_layernorm": (d,), "post_attention_layernorm": (d,),
            "q_a_proj": (d, r_q), "q_a_layernorm": (r_q,),
            "q_b_proj": (r_q, H * (d_n + d_r)),
            "kv_a_proj": (d, r + d_r), "kv_a_layernorm": (r,),
            "kv_b_proj": (r, H * (d_n + d_v)),
            "gate_proj": (d, H), "o_proj": (H * d_v, d),
        }
        self.kv_rows = ((r + d_r, str(c["dtype"])),)
        if not sliding:
            HI, dI = c["index_n_heads"], c["index_head_dim"]
            shapes.update(
                index_wq_b=(r_q, HI * dI), index_wk=(d, dI),
                index_k_norm=(dI,), index_k_norm_bias=(dI,),
                index_weights_proj=(d, HI))
            self.kv_rows += ((dI, str(c["dtype"])),)
            self.select = c["index_topk"]
        if self._dense:
            f = c["intermediate_size"]
            shapes.update(mlp_gate=(d, f), mlp_up=(d, f), mlp_down=(f, d))
        else:
            f, E = c["moe_intermediate_size"], c["num_experts"]
            fs = f * c["n_shared_experts"]
            P = c["num_experts_published"]
            shapes.update(
                router=(d, P), expert_bias=(P,),
                experts_gate=(E, d, f), experts_up=(E, d, f),
                experts_down=(E, f, d),
                shared_gate=(d, fs), shared_up=(d, fs), shared_down=(fs, d))
        super().__init__(
            shapes, c["dtype"], c["grad_req"],
            c["sliding_window"] if sliding else None,
            random=("expert_bias", "index_k_norm_bias"), **kwargs)

    def _mm(self, x, name):
        import jax.numpy as jnp
        return jnp.dot(x, self._w(name), preferred_element_type=jnp.float32)

    def _attention_inputs(self, x, positions):
        """x (B, T, d), normalised -> ``(q_n, q_r, row, w_uk, w_uv,
        index)`` as ``attend`` takes them."""
        import jax.numpy as jnp
        c, a = self._c, self._a
        dt = x.dtype
        B, T, d = x.shape
        H, r = a["heads"], a["kv_rank"]
        d_n, d_r, d_v = a["nope"], a["rope"], a["v"]
        eps, theta = c["rms_norm_eps"], a["theta"]
        s_q = math.sqrt(d / a["q_rank"]) if c["rescale"] else 1.0
        s_kv = math.sqrt(d / r) if c["rescale"] else 1.0

        def scaled(y, s):
            return (y.astype(jnp.float32) * s).astype(dt)

        c_q = scaled(rms_norm(self._mm(x, "q_a_proj").astype(dt),
                              self._w("q_a_layernorm"), eps), s_q)
        q = self._mm(c_q, "q_b_proj").astype(dt).reshape(B, T, H, d_n + d_r)
        q_n, q_r = q[..., :d_n], rotary(q[..., d_n:], positions, theta)
        kv = self._mm(x, "kv_a_proj").astype(dt)
        lat = scaled(rms_norm(kv[..., :r], self._w("kv_a_layernorm"), eps),
                     s_kv)
        k_r = rotary(kv[..., None, r:], positions, theta)[:, :, 0]
        w_kvb = self._w("kv_b_proj").reshape(r, H, d_n + d_v)
        index = None
        if not self._sliding:
            HI, dI = c["index_n_heads"], c["index_head_dim"]
            q_i = rotary(self._mm(c_q, "index_wq_b").astype(dt).reshape(
                B, T, HI, dI), positions, theta, dims=d_r)
            k = self._mm(x, "index_wk")                         # float32
            mu = jnp.mean(k, -1, keepdims=True)
            var = jnp.mean((k - mu) ** 2, -1, keepdims=True)
            k = (k - mu) * (var + _INDEX_NORM_EPS) ** -0.5 \
                * self._w("index_k_norm").astype(jnp.float32) \
                + self._w("index_k_norm_bias").astype(jnp.float32)
            k_i = rotary(k.astype(dt)[:, :, None], positions, theta,
                         dims=d_r)[:, :, 0]
            w_i = self._mm(x, "index_weights_proj") / math.sqrt(HI * dI)
            index = (q_i, w_i, k_i)
        return (q_n, q_r, jnp.concatenate([lat, k_r], -1),
                w_kvb[..., :d_n], w_kvb[..., d_n:], index)

    def _block(self, h, positions, attend, live):
        import jax
        import jax.numpy as jnp
        c, a = self._c, self._a
        eps = c["rms_norm_eps"]
        B, T, _ = h.shape
        x = rms_norm(h, self._w("input_layernorm"), eps)
        q_n, q_r, row, w_uk, w_uv, index = self._attention_inputs(
            x, positions)
        with jax.named_scope("attn.latent.window" if self._sliding
                             else "attn.latent.full"):
            o = attend(q_n, q_r, row, w_uk, w_uv,
                       1.0 / math.sqrt(a["nope"] + a["rope"]), index)
        g = jax.nn.sigmoid(self._mm(x, "gate_proj"))          # (B, T, H)
        o = (o.astype(jnp.float32) * g[..., None]).astype(h.dtype)
        h = h + self._mm(o.reshape(B, T, -1), "o_proj").astype(h.dtype)
        m, counts = swiglu_ffn(
            self, rms_norm(h, self._w("post_attention_layernorm"), eps),
            live)
        return h + m, counts


class Dots3Model(ServedDecoder):
    """Embedding -> layers -> final RMSNorm -> untied head without bias.

    ``num_hidden_layers`` layers of ``layer_types``, the first
    ``first_k_dense_replace`` dense.  ``n_routed_experts`` is what this
    chip holds, ``n_routed_experts_published`` (default: the same) what
    the router scores, ``first_expert`` where the held range starts."""

    def __init__(self, vocab_size, hidden_size, intermediate_size,
                 moe_intermediate_size, num_hidden_layers,
                 first_k_dense_replace, layer_types, num_attention_heads,
                 q_lora_rank, kv_lora_rank, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, rope_theta,
                 swa_num_attention_heads, swa_q_lora_rank, swa_kv_lora_rank,
                 swa_qk_nope_head_dim, swa_qk_rope_head_dim, swa_v_head_dim,
                 swa_rope_theta, sliding_window_size, index_n_heads,
                 index_head_dim, index_topk, n_routed_experts,
                 num_experts_per_tok, n_shared_experts=1,
                 n_routed_experts_published=None, first_expert=0,
                 norm_topk_prob=True, routed_scaling_factor=1.0,
                 apply_mla_qkv_lora_rescale=True, rms_norm_eps=1e-5,
                 max_position_embeddings=524288, dtype="float32",
                 grad_req="null", **kwargs):
        layer_types = list(layer_types)
        if len(layer_types) != num_hidden_layers:
            raise MXNetError(
                f"layer_types names {len(layer_types)} layers, "
                f"num_hidden_layers is {num_hidden_layers}")
        for i, kind in enumerate(layer_types):
            if kind not in ("sliding_attention", "full_attention"):
                raise MXNetError(f"layer {i}: no such layer type {kind!r}")
        published = int(n_routed_experts_published or n_routed_experts)
        if not 0 <= first_expert <= published - n_routed_experts:
            raise MXNetError(
                f"experts {first_expert}.."
                f"{first_expert + n_routed_experts - 1} are not among the "
                f"{published} published")
        if qk_rope_head_dim != swa_qk_rope_head_dim \
                or qk_rope_head_dim > index_head_dim:
            raise MXNetError(
                "the rotary widths of the two kinds and of the index "
                f"differ: {qk_rope_head_dim}, {swa_qk_rope_head_dim}, "
                f"index head {index_head_dim}")
        import jax.numpy as jnp

        def kind(heads, q_rank, kv_rank, nope, rope, v, theta):
            return dict(heads=int(heads), q_rank=int(q_rank),
                        kv_rank=int(kv_rank), nope=int(nope),
                        rope=int(rope), v=int(v), theta=float(theta))

        cfg = dict(
            hidden_size=int(hidden_size),
            intermediate_size=int(intermediate_size),
            moe_intermediate_size=int(moe_intermediate_size),
            full=kind(num_attention_heads, q_lora_rank, kv_lora_rank,
                      qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                      rope_theta),
            sliding=kind(swa_num_attention_heads, swa_q_lora_rank,
                         swa_kv_lora_rank, swa_qk_nope_head_dim,
                         swa_qk_rope_head_dim, swa_v_head_dim,
                         swa_rope_theta),
            sliding_window=int(sliding_window_size),
            index_n_heads=int(index_n_heads),
            index_head_dim=int(index_head_dim), index_topk=int(index_topk),
            num_experts=int(n_routed_experts),
            num_experts_published=published, first_expert=int(first_expert),
            num_experts_per_tok=int(num_experts_per_tok),
            n_shared_experts=int(n_shared_experts),
            route_norm=bool(norm_topk_prob),
            route_scale=float(routed_scaling_factor),
            rescale=bool(apply_mla_qkv_lora_rescale),
            rms_norm_eps=float(rms_norm_eps),
            # every layer states its rows: no layer keeps per-head K and V
            num_key_value_heads=1, head_dim=1,
            dtype=jnp.dtype(dtype), grad_req=grad_req)
        super().__init__(
            vocab_size, hidden_size, max_position_embeddings, cfg,
            [functools.partial(Dots3Layer, cfg, k == "sliding_attention",
                               i < first_k_dense_replace)
             for i, k in enumerate(layer_types)], grad_req, **kwargs)
