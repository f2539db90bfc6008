"""Plain SmallThinker forward (``PowerInfer/SmallThinker-21BA3B-Instruct``),
written from the published description — the config.json keys and what the
published modelling code does with them — in straightforward ``jax.numpy``:
no cache, no kernels, no batching, every held expert applied to every token
and masked by the routing.  Imports nothing of the program and takes nothing
the program made: weights come from :func:`init_params` and the seed.

The layer, for input ``h`` (T x d)::

    r   = h W_r                       # float32, from the layer's INPUT
    x   = RMSNorm(h);  q, k, v = x W_q, x W_k, x W_v      # no bias, no QK-norm
    if rope_layout[i]:  q, k = RoPE(q, k)                  # half-rotation
    a   = softmax(q k^T / sqrt(D) + mask) v                # head n reads KV head n // G
                                                           # sliding: p - W < j <= p
    h1  = h + a W_o
    y   = RMSNorm(h1)
    idx = top_k(r);  w = softmax(r[idx])                   # = softmax, top-k, renormalised
    out = h1 + sum_k w_k W_down[idx_k](relu(y W_gate[idx_k]) * (y W_up[idx_k]))

Embedding unscaled; ``logits = RMSNorm(h_L) W_head``, untied, no bias.

``assumed`` (what the config does not state and the modelling code does; also
in the configuration file): the router reads the layer's input, before the
attention norm ("router placed before attention"); softmax over the chosen
logits (``moe_primary_router_apply_softmax`` with ``norm_topk_prob``); ReGLU
experts; no bias anywhere; no QK-norm; the window convention above.  The
neuron-level sparsity predictor of the paper is an inference device, not part
of the function: left out.

``as_found`` — departures from the published description, each for a reason:

* every matrix is kept ``(in, out)`` and applied as ``x @ W`` (``nn.Linear``
  keeps ``(out, in)``): with seeded Normal weights the two are the same
  distribution, and the program adopts these arrays without a copy;
* weights are Normal(0, 0.02) rounded to bfloat16, norm gains 1;
* ``moe_num_primary_experts`` counts the experts HELD (all 64 in the
  benchmark's cell; a share in the tying test), of
  ``moe_num_primary_experts_published`` that the router scores, from
  ``first_expert`` on: what absent experts would add is left out.

``precision``: ``"float32"`` — the reference: bfloat16 weights upcast one
matrix (one expert) at a time, everything float32, products under
``default_matmul_precision("highest")``; ``"bfloat16"`` — as the
configuration states it: activations and both operands of every product in
bfloat16, accumulated in float32; norms, rotary embedding, router and softmax
in float32; ``"float8"`` — as bfloat16 with both operands of every product
(keys and values among them) rounded to ``float8_e4m3fn`` under a per-tensor
scale.  The router's own product stays float32 in all three: the description
fixes it, and a control that also re-routed would be too easy to tell apart.

Queries are taken 512 at a time and the head's rows 1,024 at a time, so that
7.7 k tokens at the published widths fit beside 7.9 GB of weights.
"""
import functools
import math
import zlib

import jax
import jax.numpy as jnp

from reference.afmoe import _rms, _rope, param_dtype
from reference.gpt2 import _fp8, seed_key


def held(cfg):
    """``(first, held, published)`` experts."""
    n = cfg["moe_num_primary_experts"]
    return (cfg.get("first_expert", 0), n,
            cfg.get("moe_num_primary_experts_published", n))


def layer_shapes(cfg, i=0):
    del i                                       # every layer is alike
    d, D = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f = cfg["moe_ffn_hidden_size"]
    _, E, P = held(cfg)
    return {"router": (d, P), "input_layernorm": (d,),
            "q_proj": (d, hq * D), "k_proj": (d, hkv * D),
            "v_proj": (d, hkv * D), "o_proj": (hq * D, d),
            "post_attention_layernorm": (d,),
            "experts_gate": (E, d, f), "experts_up": (E, d, f),
            "experts_down": (E, f, d)}


@functools.partial(jax.jit, static_argnums=(1, 2))
def _normal_leaf(key, shape, dt):
    return (0.02 * jax.random.normal(key, shape, jnp.float32)
            ).astype(jnp.bfloat16).astype(dt)


def init_params(cfg, seed):
    """``{"embed_tokens", "norm", "lm_head", "layers": [{name: array}]}`` on
    the device, in the layout ``models.smallthinker.SmallThinkerModel`` holds
    them: matrices and embeddings Normal(0, 0.02) rounded to bfloat16, norm
    gains 1, all kept in the deployment's ``param_dtype``.  A leaf's key is
    the seed's folded with its path; one compiled maker a shape."""
    dt = param_dtype(cfg)
    key = seed_key(seed)

    def leaf(path, shape):
        if len(shape) == 1:
            return jnp.ones(shape, dt)
        return _normal_leaf(
            jax.random.fold_in(key, zlib.crc32(path.encode())), shape, dt)

    d, V = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed_tokens": leaf("embed_tokens", (V, d)),
            "norm": leaf("norm", (d,)), "lm_head": leaf("lm_head", (d, V)),
            "layers": [{name: leaf(f"layers.{i}.{name}", shape)
                        for name, shape in layer_shapes(cfg, i).items()}
                       for i in range(cfg["num_hidden_layers"])]}


def _blocks(fn, x, size):
    """``fn(rows, first row's index)`` over ``x``'s rows ``size`` at a time
    (at once where they do not divide)."""
    T = x.shape[0]
    if T <= size or T % size:
        return fn(x, 0)
    out = jax.lax.map(lambda a: fn(a[0], a[1]),
                      (x.reshape(T // size, size, *x.shape[1:]),
                       jnp.arange(T // size) * size))
    return out.reshape(T, *out.shape[2:])


def _forward(params, tokens, cfg, dt, low_matmul):
    """tokens (T,) int32 -> float32 logits (T, V), causal."""
    q8 = _fp8 if low_matmul else (lambda x: x)

    def mm(x, w):                       # a product at the precision
        return jnp.dot(q8(x.astype(dt)), q8(w.astype(dt)),
                       preferred_element_type=jnp.float32)

    T = tokens.shape[0]
    d, D = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    G = hq // hkv
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    first, E, _ = held(cfg)
    pos = jnp.arange(T, dtype=jnp.int32)
    h = params["embed_tokens"][tokens].astype(dt)

    def attention(q, k, v, window):
        """q (T, hq, D), k/v (T, hkv, D): query head n on KV head n // G."""
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

        return _blocks(rows, q, 512)

    for i, p in enumerate(params["layers"]):
        # the router: float32 logits from the layer's input
        r = jnp.dot(h.astype(jnp.float32), p["router"].astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
        x = _rms(h, p["input_layernorm"], eps)
        q, k, v = (mm(x, p[n]).astype(dt).reshape(T, -1, D)
                   for n in ("q_proj", "k_proj", "v_proj"))
        if cfg["rope_layout"][i]:       # the other layers carry no position
            q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        a = attention(q, k, v, cfg["sliding_window_size"]
                      if cfg["sliding_window_layout"][i] else None)
        h = h + mm(a.reshape(T, hq * D), p["o_proj"]).astype(dt)
        y = _rms(h, p["post_attention_layernorm"], eps)
        top, idx = jax.lax.top_k(r, cfg["moe_num_active_primary_experts"])
        w = jax.nn.softmax(top, -1)

        def expert(acc, ew):            # every held expert over every token
            wg, wu, wd, e = ew
            share = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
            mid = (jax.nn.relu(mm(y, wg)) * mm(y, wu)).astype(dt)
            return acc + mm(mid, wd) * share[:, None], None

        m, _ = jax.lax.scan(
            expert, jnp.zeros((T, d), jnp.float32),
            (p["experts_gate"], p["experts_up"], p["experts_down"],
             jnp.arange(E)))
        h = h + m.astype(dt)
    x = _rms(h, params["norm"], eps)
    return _blocks(lambda rows, _: mm(rows, params["lm_head"]), x, 1024)


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
