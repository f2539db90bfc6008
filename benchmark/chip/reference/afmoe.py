"""Plain AFMoE forward (``model_type: afmoe``, arcee-ai Trinity), written from
the published description — the config.json keys and what the published
modelling code does with them — in straightforward ``jax.numpy``: no cache, no
kernels, no batching, every held expert applied to every token and masked by
the routing.  Imports nothing of the program and takes nothing the program
made: weights come from :func:`init_params` and the seed.

What the configuration file cuts is cut here the same way: this chip's share of
the query and KV heads, of the vocabulary, and ``num_experts`` experts held of
``num_experts_published`` (the router scores all of the published; what the
absent experts would add is left out, and the partial sum goes on).

Departures from the published description, each for a stated reason:

* every matrix is kept ``(in, out)`` and applied as ``x @ W`` (``nn.Linear``
  keeps ``(out, in)``): with seeded Normal weights the two are the same
  distribution, and the program adopts these arrays without a copy;
* weights are Normal(0, 0.02) rounded to bfloat16 (norm gains 1), the
  expert-choice bias Normal(0, 0.02) where a trained model's balances load;
* ``expert_bias`` is added to the sigmoid scores for the choice only, as the
  published ``e_score_correction_bias`` is; the weights use the bare scores.

``precision``:

* ``"float32"`` — the reference: the bfloat16 weights upcast (one expert, one
  matrix at a time, so 8.1 GB of weights and a float32 layer fit beside each
  other), everything float32, products under
  ``default_matmul_precision("highest")``;
* ``"bfloat16"`` — as the source serves it and the configuration states it:
  activations and both operands of every product in bfloat16, accumulated in
  float32; norms, rotary embedding, router scores and softmax in float32;
* ``"float8"`` — as bfloat16, with both operands of every product (keys and
  values among them) rounded to ``float8_e4m3fn`` under a per-tensor scale.
  The router's own product stays float32: the description fixes it, and a
  control that also re-routed would be too easy to tell apart.
"""
import functools
import math
import zlib

import jax
import jax.numpy as jnp

from reference.gpt2 import _fp8, seed_key


def param_dtype(cfg):
    return jnp.dtype(cfg.get("deployment", {}).get("param_dtype", "bfloat16"))


def layer_shapes(cfg, i):
    d, D = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    s = {"input_layernorm": (d,), "post_attention_layernorm": (d,),
         "pre_mlp_layernorm": (d,), "post_mlp_layernorm": (d,),
         "q_proj": (d, hq * D), "k_proj": (d, hkv * D), "v_proj": (d, hkv * D),
         "gate_proj": (d, hq * D), "o_proj": (hq * D, d),
         "q_norm": (D,), "k_norm": (D,)}
    if i < cfg["num_dense_layers"]:
        f = cfg["intermediate_size"]
        s.update(mlp_gate=(d, f), mlp_up=(d, f), mlp_down=(f, d))
    else:
        f, E = cfg["moe_intermediate_size"], cfg["num_experts"]
        fs = f * cfg["num_shared_experts"]
        P = cfg.get("num_experts_published", E)
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
    the device: matrices, embeddings and the expert bias Normal(0, 0.02)
    rounded to bfloat16, norm gains 1, all kept in the deployment's
    ``param_dtype``.  A leaf's key is the seed's folded with its path; one
    compiled maker a shape."""
    dt = param_dtype(cfg)
    key = seed_key(seed)

    def leaf(path, shape):
        if len(shape) == 1 and not path.endswith("expert_bias"):
            return jnp.ones(shape, dt)
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


def _rope(x, pos, theta):
    """Half-rotation rotary embedding of x (T, H, D) at positions pos (T,)."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    xf = x.astype(jnp.float32)
    rot = jnp.concatenate([-xf[..., D // 2:], xf[..., :D // 2]], -1)
    return (xf * cos + rot * sin).astype(x.dtype)


def _forward(params, tokens, cfg, dt, low_matmul):
    """tokens (T,) int32 -> float32 logits (T, V), causal."""
    q8 = _fp8 if low_matmul else (lambda x: x)

    def mm(x, w):                       # a product at the precision
        return jnp.dot(q8(x.astype(dt)), q8(w.astype(dt)),
                       preferred_element_type=jnp.float32)

    def swiglu(x, wg, wu, wd):
        mid = (jax.nn.silu(mm(x, wg)) * mm(x, wu)).astype(dt)
        return mm(mid, wd)

    T = tokens.shape[0]
    d, D = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    G = hq // hkv
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(T, dtype=jnp.int32)
    h = params["embed_tokens"][tokens].astype(dt)
    if cfg.get("mup_enabled"):
        h = (h.astype(jnp.float32) * math.sqrt(d)).astype(dt)

    def attention(q, k, v, window):
        """q (T, hq, D), k/v (T, hkv, D); queries a block of 1024 at a time."""
        kq, vq = q8(k), q8(v)

        def rows(qb, i0):
            qg = q8(qb).reshape(-1, hkv, G, D)
            s = jnp.einsum("qkgd,tkd->kgqt", qg, kq,
                           preferred_element_type=jnp.float32) / math.sqrt(D)
            qi = (i0 + jnp.arange(qb.shape[0]))[:, None]
            live = pos[None, :] <= qi
            if window is not None:
                live = live & (pos[None, :] > qi - window)
            p = jax.nn.softmax(jnp.where(live[None, None], s, -1e30), -1)
            o = jnp.einsum("kgqt,tkd->qkgd", q8(p.astype(dt)), vq,
                           preferred_element_type=jnp.float32)
            return o.reshape(-1, hq, D).astype(dt)

        blk = 1024
        if T <= blk or T % blk:
            return rows(q, 0)
        out = jax.lax.map(lambda a: rows(a[0], a[1]),
                          (q.reshape(T // blk, blk, hq, D),
                           jnp.arange(T // blk) * blk))
        return out.reshape(T, hq, D)

    for i, p in enumerate(params["layers"]):
        sliding = cfg["layer_types"][i] == "sliding_attention"
        x = _rms(h, p["input_layernorm"], eps)
        q, k, v, g = (mm(x, p[n]).astype(dt).reshape(T, -1, D)
                      for n in ("q_proj", "k_proj", "v_proj", "gate_proj"))
        q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
        if sliding:                     # full layers carry no position
            q = _rope(q, pos, float(cfg["rope_theta"]))
            k = _rope(k, pos, float(cfg["rope_theta"]))
        a = attention(q, k, v, cfg["sliding_window"] if sliding else None)
        a = (a.astype(jnp.float32)
             * jax.nn.sigmoid(g.astype(jnp.float32))).astype(dt)
        o = mm(a.reshape(T, hq * D), p["o_proj"]).astype(dt)
        h = h + _rms(o, p["post_attention_layernorm"], eps)
        x = _rms(h, p["pre_mlp_layernorm"], eps)
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
            if cfg.get("route_norm"):
                w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
            w = w * cfg.get("route_scale", 1.0)
            first = cfg.get("first_expert", 0)

            def expert(acc, ew):        # every held expert over every token
                wg, wu, wd, e = ew
                share = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
                return acc + swiglu(x, wg, wu, wd) * share[:, None], None

            m, _ = jax.lax.scan(
                expert, jnp.zeros((T, d), jnp.float32),
                (p["experts_gate"], p["experts_up"], p["experts_down"],
                 jnp.arange(cfg["num_experts"])))
            m = m + swiglu(x, p["shared_gate"], p["shared_up"],
                           p["shared_down"])
        h = h + _rms(m.astype(dt), p["post_mlp_layernorm"], eps)
    return mm(_rms(h, params["norm"], eps), params["lm_head"])


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
