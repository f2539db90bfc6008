"""Plain dots3-note forward (``model_type: dots3_note``,
dots-studio/dots3-note-prev), written from the published description — the
config.json keys, each a mechanism with a published form (latent attention as
DeepSeek-V2/V3 state it, the lightning indexer of DeepSeek-V3.2-Exp, a
head-wise sigmoid gate, sigmoid routing with a choice-only bias, SwiGLU experts
and a shared expert) — in straightforward ``jax.numpy``: no cache, no kernels,
no batching, UNabsorbed attention (every position's latent expanded to its
heads' keys and values), the index scores a dense (queries, T) array a block
of queries at a time with the chosen set scattered into a mask, every held
expert applied to every token and masked by the routing.  Imports nothing of
the program and takes nothing the program made: weights come from
:func:`init_params` and the seed.

For a layer of input ``h``, ``x = RMSNorm(h)``::

    c_q           = s_q RMSNorm(x W_qa)                    s_q  = sqrt(d / r_q)
    [q_n | q_r]_h = c_q W_qb (per head);  q_r = RoPE(q_r)
    [c | k_r]     = x W_kva;  c = s_kv RMSNorm(c);  k_r = RoPE(k_r)
                                                          s_kv = sqrt(d / r_kv)
    [k_n | v]_h   = c W_kvb (per head)
    a_h(t, s)     = (q_n,h(t) . k_n,h(s) + q_r,h(t) . k_r(s)) / sqrt(d_n + d_r)
    o_h           = sigmoid(x W_g)_h sum_s softmax_s(a_h)(t, s) v_h(s)
    h1 = h + concat_h(o_h) W_o;   out = h1 + FFN(RMSNorm(h1))

over the allowed ``s``: in a sliding layer ``t - window < s <= t``; in a full
layer the ``min(k, t + 1)`` positions ``s <= t`` of largest ``I(t, s) = sum_j
w_j(t) relu(q_I,j(t) . k_I(s))``, ``q_I = RoPE64(c_q W_Iq)``, ``k_I =
RoPE64(LayerNorm(x W_Ik))``, ``w = x W_Iw / sqrt(H_I d_I)``.

What the configuration file cuts is cut here the same way: this chip's share of
the heads of either kind, of the vocabulary, and ``n_routed_experts`` experts
held of ``n_routed_experts_published`` (the router scores all of the published;
what the absent experts would add is left out, and the partial sum goes on).
The indexer is whole.

Departures from the published description, each for a stated reason (the
configuration file's ``assumed`` says the same):

* every matrix is kept ``(in, out)`` and applied as ``x @ W``; a per-head
  matrix's columns are head-major, ``[nope | rope]`` and ``[nope | v]`` within
  a head; half-rotation rotary, the index's on its first ``qk_rope_head_dim``
  features;
* ``apply_mla_qkv_lora_rescale``: the normalised latents are multiplied by
  ``sqrt(hidden_size / rank)`` (the one public convention for the flag);
* the Hadamard rotation of the index queries and keys (orthogonal: it changes
  no score) and their FP8 storage are left out;
* weights are Normal(0, 0.02) rounded to bfloat16; norm gains 1, the index
  key's LayerNorm bias and the experts' choice bias 0.

``precision``: ``"float32"`` the reference (everything float32, products under
``default_matmul_precision("highest")``, rows a block at a time so that a
27 k-token sequence fits beside 7.3 GB of weights); ``"bfloat16"`` as the
configuration states it (operands of every product bfloat16, accumulated in
float32; norms, rotary, router, index ReLU-and-sum and softmax float32);
``"float8"`` as bfloat16 with both operands of every product — cached latents
and index keys among them — rounded to ``float8_e4m3fn`` under a per-tensor
scale (the router's own product stays float32, as the description fixes it).
"""
import functools
import math
import zlib

import jax
import jax.numpy as jnp

from reference.gpt2 import _fp8, seed_key

INDEX_NORM_EPS = 1e-6
#: vectors that start at zero (every other vector is a norm gain: ones)
ZEROS = ("expert_bias", "index_k_norm_bias")


def param_dtype(cfg):
    return jnp.dtype(cfg.get("deployment", {}).get("param_dtype", "bfloat16"))


def kind(cfg, i):
    """Layer ``i``'s attention sizes: ``(sliding, H, r_q, r_kv, d_n, d_r, d_v,
    theta)``."""
    if cfg["layer_types"][i] == "sliding_attention":
        return (True, cfg["swa_num_attention_heads"], cfg["swa_q_lora_rank"],
                cfg["swa_kv_lora_rank"], cfg["swa_qk_nope_head_dim"],
                cfg["swa_qk_rope_head_dim"], cfg["swa_v_head_dim"],
                float(cfg["swa_rope_theta"]))
    return (False, cfg["num_attention_heads"], cfg["q_lora_rank"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            float(cfg["rope_theta"]))


def layer_shapes(cfg, i):
    d = cfg["hidden_size"]
    sliding, H, r_q, r, d_n, d_r, d_v, _ = kind(cfg, i)
    s = {"input_layernorm": (d,), "post_attention_layernorm": (d,),
         "q_a_proj": (d, r_q), "q_a_layernorm": (r_q,),
         "q_b_proj": (r_q, H * (d_n + d_r)),
         "kv_a_proj": (d, r + d_r), "kv_a_layernorm": (r,),
         "kv_b_proj": (r, H * (d_n + d_v)),
         "gate_proj": (d, H), "o_proj": (H * d_v, d)}
    if not sliding:
        HI, dI = cfg["index_n_heads"], cfg["index_head_dim"]
        s.update(index_wq_b=(r_q, HI * dI), index_wk=(d, dI),
                 index_k_norm=(dI,), index_k_norm_bias=(dI,),
                 index_weights_proj=(d, HI))
    if i < cfg["first_k_dense_replace"]:
        f = cfg["intermediate_size"]
        s.update(mlp_gate=(d, f), mlp_up=(d, f), mlp_down=(f, d))
    else:
        f, E = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
        fs = f * cfg["n_shared_experts"]
        P = cfg.get("n_routed_experts_published", E)
        s.update(router=(d, P), expert_bias=(P,),
                 experts_gate=(E, d, f), experts_up=(E, d, f),
                 experts_down=(E, f, d),
                 shared_gate=(d, fs), shared_up=(d, fs), shared_down=(fs, d))
    return s


@functools.partial(jax.jit, static_argnums=(1, 2))
def _normal_leaf(key, shape, dt):
    return (0.02 * jax.random.normal(key, shape, jnp.float32)
            ).astype(jnp.bfloat16).astype(dt)


def init_params(cfg, seed):
    """``{"embed_tokens", "norm", "lm_head", "layers": [{name: array}]}`` on
    the device, in the deployment's ``param_dtype``: matrices and embeddings
    Normal(0, 0.02) rounded to bfloat16, norm gains 1, the two biases 0.  A
    leaf's key is the seed's folded with its path; one compiled maker a
    shape."""
    dt = param_dtype(cfg)
    key = seed_key(seed)

    def leaf(path, shape):
        if len(shape) == 1:
            return (jnp.zeros if path.rsplit(".", 1)[-1] in ZEROS
                    else jnp.ones)(shape, dt)
        return _normal_leaf(
            jax.random.fold_in(key, zlib.crc32(path.encode())), shape, dt)

    d, V = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed_tokens": leaf("embed_tokens", (V, d)),
            "norm": leaf("norm", (d,)), "lm_head": leaf("lm_head", (d, V)),
            "layers": [{name: leaf(f"layers.{i}.{name}", shape)
                        for name, shape in layer_shapes(cfg, i).items()}
                       for i in range(cfg["num_hidden_layers"])]}


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, theta, dims=None):
    """Half-rotation rotary embedding of x (T, H, D) at positions pos (T,);
    with ``dims`` of a head's first ``dims`` features only."""
    if dims is not None and dims != x.shape[-1]:
        return jnp.concatenate([_rope(x[..., :dims], pos, theta),
                                x[..., dims:]], -1)
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    xf = x.astype(jnp.float32)
    rot = jnp.concatenate([-xf[..., D // 2:], xf[..., :D // 2]], -1)
    return (xf * cos + rot * sin).astype(x.dtype)


def _by_rows(f, blk, *xs):
    """``f`` over the leading axis of ``xs``, ``blk`` rows at a time where
    they divide (a 27 k-token sequence's intermediates must not all exist at
    once), all at once where they do not."""
    T = xs[0].shape[0]
    if T <= blk or T % blk:
        return f(*xs)
    cut = jax.tree.map(
        lambda x: x.reshape((T // blk, blk) + x.shape[1:]), xs)
    out = jax.lax.map(lambda a: f(*a), cut)
    return jax.tree.map(lambda o: o.reshape((T,) + o.shape[2:]), out)


def _forward(params, tokens, cfg, dt, low, chosen=None):
    """tokens (T,) int32 -> float32 logits (T, V), causal.  ``chosen``: a
    list that receives each full layer's (T, T) mask of chosen keys."""
    q8 = _fp8 if low else (lambda x: x)

    def mm(x, w):                       # a product at the precision
        return jnp.dot(q8(x.astype(dt)), q8(w.astype(dt)),
                       preferred_element_type=jnp.float32)

    def swiglu(x, wg, wu, wd):
        mid = (jax.nn.silu(mm(x, wg)) * mm(x, wu)).astype(dt)
        return mm(mid, wd)

    T = tokens.shape[0]
    d = cfg["hidden_size"]
    eps = cfg["rms_norm_eps"]
    rescale = cfg.get("apply_mla_qkv_lora_rescale", False)
    pos = jnp.arange(T, dtype=jnp.int32)
    h = params["embed_tokens"][tokens].astype(dt)

    for i, p in enumerate(params["layers"]):
        sliding, H, r_q, r, d_n, d_r, d_v, theta = kind(cfg, i)
        s_q = math.sqrt(d / r_q) if rescale else 1.0
        s_kv = math.sqrt(d / r) if rescale else 1.0

        def scaled(y, s):
            return (y.astype(jnp.float32) * s).astype(dt)

        def project(hb, posb):
            """A block of rows -> what attention needs of them."""
            x = _rms(hb, p["input_layernorm"], eps)
            c_q = scaled(_rms(mm(x, p["q_a_proj"]).astype(dt),
                              p["q_a_layernorm"], eps), s_q)
            q = mm(c_q, p["q_b_proj"]).astype(dt).reshape(-1, H, d_n + d_r)
            q = jnp.concatenate(
                [q[..., :d_n], _rope(q[..., d_n:], posb, theta)], -1)
            kv = mm(x, p["kv_a_proj"]).astype(dt)
            c = scaled(_rms(kv[:, :r], p["kv_a_layernorm"], eps), s_kv)
            k_r = _rope(kv[:, None, r:], posb, theta)           # (T, 1, d_r)
            kv_h = mm(c, p["kv_b_proj"]).astype(dt).reshape(-1, H, d_n + d_v)
            k = jnp.concatenate(
                [kv_h[..., :d_n], jnp.broadcast_to(k_r, (k_r.shape[0], H,
                                                         d_r))], -1)
            out = {"q": q, "k": k, "v": kv_h[..., d_n:],
                   "g": jax.nn.sigmoid(mm(x, p["gate_proj"]))}
            if not sliding:
                HI, dI = cfg["index_n_heads"], cfg["index_head_dim"]
                out["q_i"] = _rope(
                    mm(c_q, p["index_wq_b"]).astype(dt).reshape(-1, HI, dI),
                    posb, theta, d_r)
                k_i = mm(x, p["index_wk"])
                mu = jnp.mean(k_i, -1, keepdims=True)
                var = jnp.mean((k_i - mu) ** 2, -1, keepdims=True)
                k_i = (k_i - mu) * (var + INDEX_NORM_EPS) ** -0.5 \
                    * p["index_k_norm"].astype(jnp.float32) \
                    + p["index_k_norm_bias"].astype(jnp.float32)
                out["k_i"] = _rope(k_i.astype(dt)[:, None], posb, theta,
                                   d_r)[:, 0]
                out["w_i"] = mm(x, p["index_weights_proj"]) \
                    / math.sqrt(HI * dI)
            return out

        a = _by_rows(project, 512, h, pos)
        kq, vq = q8(a["k"]), q8(a["v"])
        k_i = None if sliding else q8(a["k_i"])

        def attend(qb, gb, posb, sel):
            """A block of queries over every position."""
            live = pos[None, :] <= posb[:, None]                # (qb, T)
            if sliding:
                live = live & (pos[None, :]
                               > posb[:, None] - cfg["sliding_window_size"])
            else:
                q_i, w_i = sel
                s = jnp.einsum("qhd,td->qht", q8(q_i), k_i,
                               preferred_element_type=jnp.float32)
                s = jnp.sum(jax.nn.relu(s) * w_i[..., None], 1)   # (qb, T)
                vals, idx = jax.lax.top_k(
                    jnp.where(live, s, -jnp.inf),
                    min(cfg["index_topk"], T))
                rows = jnp.arange(qb.shape[0])[:, None]
                live = jnp.zeros(live.shape, bool).at[rows, idx].set(
                    vals > -jnp.inf)
            s = jnp.einsum("qhd,thd->hqt", q8(qb), kq,
                           preferred_element_type=jnp.float32) \
                / math.sqrt(d_n + d_r)
            pr = jax.nn.softmax(jnp.where(live[None], s, -1e30), -1)
            o = jnp.einsum("hqt,thd->qhd", q8(pr.astype(dt)), vq,
                           preferred_element_type=jnp.float32)
            o = (o * gb[..., None]).astype(dt)
            return (o, live) if chosen is not None and not sliding else (o,)

        got = _by_rows(attend, 512 if sliding else 64, a["q"], a["g"], pos,
                       None if sliding else (a["q_i"], a["w_i"]))
        if len(got) > 1:
            chosen.append(got[1])
        o = got[0].reshape(T, H * d_v)

        def rest(hb, ob):
            """A block of rows: the output projection and the FFN."""
            hb = hb + mm(ob, p["o_proj"]).astype(dt)
            x = _rms(hb, p["post_attention_layernorm"], eps)
            if "mlp_gate" in p:
                m = swiglu(x, p["mlp_gate"], p["mlp_up"], p["mlp_down"])
            else:
                # the router: float32 scores over all published experts
                s = jax.nn.sigmoid(jnp.dot(
                    x.astype(jnp.float32), p["router"].astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST))
                _, idx = jax.lax.top_k(
                    s + p["expert_bias"].astype(jnp.float32)[None],
                    cfg["num_experts_per_tok"])
                w = jnp.take_along_axis(s, idx, -1)
                if cfg.get("norm_topk_prob"):
                    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
                w = w * cfg.get("routed_scaling_factor", 1.0)
                first = cfg.get("first_expert", 0)

                def expert(acc, ew):    # every held expert over every token
                    wg, wu, wd, e = ew
                    share = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
                    return acc + swiglu(x, wg, wu, wd) * share[:, None], None

                m, _ = jax.lax.scan(
                    expert, jnp.zeros(x.shape, jnp.float32),
                    (p["experts_gate"], p["experts_up"], p["experts_down"],
                     jnp.arange(cfg["n_routed_experts"])))
                m = m + swiglu(x, p["shared_gate"], p["shared_up"],
                               p["shared_down"])
            return hb + m.astype(dt)

        h = _by_rows(rest, 512, h, o)
    return _by_rows(lambda hb: mm(_rms(hb, params["norm"], eps),
                                  params["lm_head"]), 512, h)


def make_forward(cfg, precision="float32"):
    """A jitted ``(params, tokens (B, T)) -> float32 logits (B, T, V)``."""
    if precision == "float32":
        def one(params, toks):
            with jax.default_matmul_precision("highest"):
                return _forward(params, toks, cfg, jnp.float32, False)
    elif precision in ("bfloat16", "float8"):
        def one(params, toks):
            return _forward(params, toks, cfg, jnp.bfloat16,
                            precision == "float8")
    else:
        raise ValueError(f"no such precision: {precision!r}")
    return jax.jit(lambda params, tokens: jnp.stack(
        [one(params, t) for t in tokens]))


def chosen_sets(cfg, params, tokens):
    """The float32 reference's choice: for each full layer, the (T, T) mask of
    the keys each position attends over (for the tests that hold the
    program's choice to it)."""
    out = []
    with jax.default_matmul_precision("highest"):
        _forward(params, jnp.asarray(tokens, jnp.int32), cfg, jnp.float32,
                 False, out)
    return out
