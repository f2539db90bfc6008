"""Plain Qwen3-Next forward (``Qwen/Qwen3-Next-80B-A3B-Instruct``), written
from the published description — the config.json keys and what the published
modelling code does with them — in straightforward ``jax.numpy``: no cache, no
kernels, no chunks, no batching; the delta rule token by token
(``lax.scan``), full softmax attention, every held expert applied to every
token and masked by the routing.  Imports nothing of the program and takes
nothing the program made: weights come from :func:`init_params` and the seed.

Layer ``l`` is full attention where ``(l + 1) % full_attention_interval == 0``,
else a Gated DeltaNet.  Every RMSNorm but the DeltaNet's own is zero-centred,
``x_hat (1 + w)``.  For input ``h`` (T x d)::

    x  = norm1(h)
    DeltaNet:  [q, k, v, z] = x W_qkvz;  [b, a] = x W_ba
               (q, k, v) <- silu(depthwise causal conv over 4 positions, no bias)
               per head: q, k <- x / sqrt(sum x^2 + 1e-6);  q <- q / sqrt(Dk)
               value head j reads key head j // (Hv / Hk)
               beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
               S <- e^g S;  u = beta (v - S^T k);  S <- S + k u^T;  o = S^T q
               mix = (RMSNorm(o; w) silu(z)) W_o           # plain weight
    full:      [q | gate] = x W_q;  k, v = x W_k, x W_v;  q, k <- norm per head
               RoPE on the first partial_rotary_factor of a head's features
               mix = (softmax(q k^T / sqrt(D), causal) v sigmoid(gate)) W_o
    h1 = h + mix;  y = norm2(h1)
    p  = softmax(y W_r);  idx = top_k(p);  w = p[idx] / sum p[idx]
    out = h1 + sum_k w_k SwiGLU_{idx_k}(y) + sigmoid(y . w_s) SwiGLU_shared(y)

Embedding unscaled; ``logits = norm(h_L) W_head``, untied, no bias.

``assumed`` (also in the configuration file): the multi-token-prediction
module is left out (no key of the config, not part of the next-token
function); the column order inside ``W_qkvz``, ``W_ba`` and ``W_q`` is
``[q | k | v | z]``, ``[b | a]`` and ``(head, [q, gate], D)`` — with seeded
weights the same distribution as the source's interleaving.

``as_found`` — departures from the published description, each for a reason:

* every matrix is kept ``(in, out)`` and applied as ``x @ W``; the program
  adopts these arrays without a copy;
* matrices and embeddings are Normal(0, 0.02) rounded to bfloat16; the
  convolution's taps Normal(0, 0.3) (a fan-in of 4); zero-centred norm
  weights Normal(0, 0.1) and the DeltaNet norm's 1 + Normal(0, 0.1), so that
  ``1 + w`` and ``w`` cannot be mistaken for one another; ``A_log = log
  U(1, 16)`` and ``dt_bias = softplus^-1(dt)``, ``dt`` log-uniform over
  [0.001, 0.1] (the Mamba-2 / Gated DeltaNet initialisation): heads whose
  state forgets within a few tokens and heads that carry it for thousands;
* ``num_experts``, the four head counts and ``vocab_size`` count what is HELD
  (``num_experts_published`` experts are scored, from ``first_expert`` on):
  what the absent experts and heads would add is left out.

``precision``: ``"float32"`` — the reference: bfloat16 weights upcast one
matrix (one expert) at a time, everything float32, products under
``default_matmul_precision("highest")``; ``"bfloat16"`` — as the configuration
states it: activations and both operands of every product in bfloat16,
accumulated in float32; norms, rotary embedding, router, softmax, the gates
``g`` and ``beta`` and the state in float32; ``"float8"`` — as bfloat16 with
both operands of every product (keys, values and the delta rule's q, k, v
among them) rounded to ``float8_e4m3fn`` under a per-tensor scale.  The
router's own product stays float32 in all three.

Queries are taken 512 at a time, so that 17.4 k tokens at the published
widths fit beside 7.2 GB of weights; the head is one product (see there).
"""
import functools
import math
import zlib

import jax
import jax.numpy as jnp

from reference.afmoe import _rms, _rope, param_dtype
from reference.gpt2 import _fp8, seed_key
from reference.smallthinker import _blocks


def held(cfg):
    """``(first, held, published)`` experts."""
    n = cfg["num_experts"]
    return cfg.get("first_expert", 0), n, cfg.get("num_experts_published", n)


def is_linear(cfg, i):
    return (i + 1) % cfg["full_attention_interval"] != 0


def _linear_dims(cfg):
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return hk, hv, dk, dv, 2 * hk * dk + hv * dv


def layer_shapes(cfg, i=0):
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    fs = cfg["shared_expert_intermediate_size"]
    _, E, P = held(cfg)
    s = {"input_layernorm": (d,), "post_attention_layernorm": (d,)}
    if is_linear(cfg, i):
        _, hv, _, dv, conv = _linear_dims(cfg)
        s.update(in_proj_qkvz=(d, conv + hv * dv), in_proj_ba=(d, 2 * hv),
                 conv1d=(cfg["linear_conv_kernel_dim"], conv), dt_bias=(hv,),
                 A_log=(hv,), norm=(dv,), out_proj=(hv * dv, d))
    else:
        D = cfg["head_dim"]
        hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        s.update(q_proj=(d, hq * 2 * D), k_proj=(d, hkv * D),
                 v_proj=(d, hkv * D), q_norm=(D,), k_norm=(D,),
                 o_proj=(hq * D, d))
    s.update(router=(d, P), experts_gate=(E, d, f), experts_up=(E, d, f),
             experts_down=(E, f, d), shared_gate=(d, fs), shared_up=(d, fs),
             shared_down=(fs, d), shared_expert_gate=(d, 1))
    return s


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _leaf(key, shape, dt, kind):
    if kind == "A_log":
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif kind == "dt_bias":
        dt_ = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                         math.log(1e-3), math.log(1e-1)))
        x = dt_ + jnp.log(-jnp.expm1(-dt_))          # softplus^-1
    else:
        std, mean = {"matrix": (0.02, 0.0), "conv1d": (0.3, 0.0),
                     "centred": (0.1, 0.0), "norm": (0.1, 1.0)}[kind]
        x = mean + std * jax.random.normal(key, shape, jnp.float32)
    return x.astype(jnp.bfloat16).astype(dt)


def init_params(cfg, seed):
    """``{"embed_tokens", "norm", "lm_head", "layers": [{name: array}]}`` on
    the device, in the layout ``models.qwen3_next.Qwen3NextModel`` holds
    them, all kept in the deployment's ``param_dtype`` (``as_found`` above
    says which leaf is drawn how).  A leaf's key is the seed's folded with
    its path; one compiled maker a shape and kind."""
    dt = param_dtype(cfg)
    key = seed_key(seed)

    def leaf(path, shape):
        name = path.rsplit(".", 1)[-1]
        kind = name if name in ("A_log", "dt_bias", "conv1d", "norm") \
            and "layers" in path else \
            "centred" if len(shape) == 1 else "matrix"
        return _leaf(jax.random.fold_in(key, zlib.crc32(path.encode())),
                     tuple(shape), dt, kind)

    d, V = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed_tokens": leaf("embed_tokens", (V, d)),
            "norm": leaf("norm", (d,)), "lm_head": leaf("lm_head", (d, V)),
            "layers": [{name: leaf(f"layers.{i}.{name}", shape)
                        for name, shape in layer_shapes(cfg, i).items()}
                       for i in range(cfg["num_hidden_layers"])]}


def _centred(x, w, eps):
    return _rms(x, 1.0 + w.astype(jnp.float32), eps)


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
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    turned = int(D * cfg["partial_rotary_factor"])
    first, E, _ = held(cfg)
    pos = jnp.arange(T, dtype=jnp.int32)
    h = params["embed_tokens"][tokens].astype(dt)

    def attention(q, k, v):
        """q (T, hq, D), k/v (T, hkv, D): query head n on KV head n // G."""
        kq, vq = q8(k), q8(v)

        def rows(qb, i0):
            qg = q8(qb).reshape(-1, hkv, G, D)
            s = jnp.einsum("qkgd,tkd->kgqt", qg, kq,
                           preferred_element_type=jnp.float32) / math.sqrt(D)
            qi = (i0 + jnp.arange(qb.shape[0]))[:, None]
            p = jax.nn.softmax(
                jnp.where((pos[None, :] <= qi)[None, None], s, -1e30), -1)
            o = jnp.einsum("kgqt,tkd->qkgd", q8(p.astype(dt)), vq,
                           preferred_element_type=jnp.float32)
            return o.reshape(-1, hq, D).astype(dt)

        return _blocks(rows, q, 512)

    def rope(x):        # the first ``turned`` features of a head only
        return jnp.concatenate(
            [_rope(x[..., :turned], pos, theta), x[..., turned:]], -1)

    def full(x, p):
        qg = mm(x, p["q_proj"]).astype(dt).reshape(T, hq, 2, D)
        q, gate = qg[:, :, 0], qg[:, :, 1]
        k, v = (mm(x, p[n]).astype(dt).reshape(T, hkv, D)
                for n in ("k_proj", "v_proj"))
        q = rope(_centred(q, p["q_norm"], eps))
        k = rope(_centred(k, p["k_norm"], eps))
        a = attention(q, k, v).astype(jnp.float32) \
            * jax.nn.sigmoid(gate.astype(jnp.float32))
        return mm(a.astype(dt).reshape(T, hq * D), p["o_proj"])

    def delta_net(x, p):
        hk, hv, dk, dv, conv = _linear_dims(cfg)
        K = cfg["linear_conv_kernel_dim"]
        qkvz = mm(x, p["in_proj_qkvz"]).astype(dt)
        ba = mm(x, p["in_proj_ba"])                         # float32
        beta = jax.nn.sigmoid(ba[:, :hv])
        g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            ba[:, hv:] + p["dt_bias"].astype(jnp.float32))
        # the convolution: position t reads inputs t - 3 .. t, zeros before 0
        seq = jnp.pad(qkvz[:, :conv].astype(jnp.float32),
                      ((K - 1, 0), (0, 0)))
        taps = p["conv1d"].astype(jnp.float32)
        mixed = jax.nn.silu(sum(seq[j:j + T] * taps[j] for j in range(K))
                            ).astype(dt)
        q, k = (mixed[:, i * hk * dk:(i + 1) * hk * dk].astype(jnp.float32)
                .reshape(T, hk, dk) for i in (0, 1))
        q, k = (a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)
                for a in (q, k))
        q, k = (jnp.repeat(a, hv // hk, axis=1)
                for a in (q / math.sqrt(dk), k))
        v = mixed[:, 2 * hk * dk:].astype(jnp.float32).reshape(T, hv, dv)
        q, k, v = q8(q), q8(k), q8(v)

        def token(S, x):
            qt, kt, vt, gt, bt = x
            S = jnp.exp(gt)[:, None, None] * S
            u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt))
            S = S + kt[:, :, None] * u[:, None, :]
            return S, jnp.einsum("hkv,hk->hv", S, qt)

        with jax.default_matmul_precision("highest"):   # the state is float32
            _, o = jax.lax.scan(token, jnp.zeros((hv, dk, dv), jnp.float32),
                                (q, k, v, g, beta))
        z = qkvz[:, conv:].astype(jnp.float32).reshape(T, hv, dv)
        y = _rms(o, p["norm"], eps) * jax.nn.silu(z)
        return mm(y.astype(dt).reshape(T, hv * dv), p["out_proj"])

    for i, p in enumerate(params["layers"]):
        x = _centred(h, p["input_layernorm"], eps)
        h = h + (delta_net if is_linear(cfg, i) else full)(x, p).astype(dt)
        y = _centred(h, p["post_attention_layernorm"], eps)
        r = jnp.dot(y.astype(jnp.float32), p["router"].astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
        top, idx = jax.lax.top_k(jax.nn.softmax(r, -1),
                                 cfg["num_experts_per_tok"])
        w = top / jnp.sum(top, -1, keepdims=True)

        def expert(acc, ew):            # every held expert over every token
            wg, wu, wd, e = ew
            share = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
            return acc + swiglu(y, wg, wu, wd) * share[:, None], None

        m, _ = jax.lax.scan(
            expert, jnp.zeros((T, d), jnp.float32),
            (p["experts_gate"], p["experts_up"], p["experts_down"],
             jnp.arange(E)))
        gate = jax.nn.sigmoid(mm(y, p["shared_expert_gate"]))      # (T, 1)
        m = m + gate * swiglu(y, p["shared_gate"], p["shared_up"],
                              p["shared_down"])
        h = h + m.astype(dt)
    # one product, written where the caller reads it: taken 1,024 rows at a
    # time the 5.3 GB of float32 logits would exist twice (the loop's buffer
    # and the result), which 17.4 k tokens beside 7.2 GB of weights do not
    # leave room for
    return mm(_centred(h, params["norm"], eps), params["lm_head"])


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
