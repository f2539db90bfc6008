"""Plain Granite 4.0-H forward (``ibm-granite/granite-4.0-h-micro``), written
from the published description — the config.json keys and what the published
modelling code does with them — in straightforward ``jax.numpy``: no cache, no
kernels, no chunks, no batching, no stacking; the Mamba-2 recurrence token by
token (``lax.scan``), full softmax attention on heads of ``hidden_size /
num_attention_heads``.  Imports nothing of the program and takes nothing the
program made: weights come from :func:`init_params` and the seed.

``layer_types[i]`` says whether layer ``i`` mixes with Mamba-2 or attention;
every layer ends in the same SwiGLU MLP.  For input ids (T,)::

    h = embedding_multiplier * E[ids]
    x = rmsnorm(h; w1)
    mamba:      [z | xBC | dt] = x W_in          # inner, inner + 2 N, H columns
                xBC <- silu(depthwise causal conv over 4 positions + bias)
                xBC -> x_h (H heads x P), B (N), C (N)    # one B, C for all heads
                dt_h = softplus(dt_h + dt_bias_h);  a_h = exp(-exp(A_log_h) dt_h)
                S_h <- a_h S_h + dt_h x_h B^T  (P x N, float32);  y_h = S_h C + D_h x_h
                mix = rmsnorm(y * silu(z); w_n) W_out     # the norm over all H P features
    attention:  q, k, v = x W_q, x W_k, x W_v;  query head n on KV head n // (Hq / Hkv)
                mix = softmax(attention_multiplier q k^T, causal) v W_o   # no positions
    h = h + residual_multiplier * mix
    h = h + residual_multiplier * W_down (silu(y W_gate) * (y W_up)),  y = rmsnorm(h; w2)
    logits = rmsnorm(h_L; w_f) E^T / logits_scaling       # the head is the embedding

``assumed`` (also in the configuration file), from memory of the published
modelling code and not from a config key: the column order ``[z | xBC | dt]``;
the gate ``silu(z)`` applied BEFORE the inner norm; ``dt`` unclamped above;
``D`` a scalar a head; ``B`` and ``C`` shared by all heads (``mamba_n_groups``
1); the state kept in float32.

``as_found`` — departures from the published description, each for a reason:

* every matrix is kept ``(in, out)`` and applied as ``x @ W``; the program
  adopts these arrays (stacking each run of Mamba layers as it goes);
* matrices are Normal(0, 0.02) rounded to bfloat16; the embedding Normal(0,
  0.02 / embedding_multiplier), so that the scaled embedding has the
  matrices' 0.02 — at 0.02 itself the tied head would find ``12 E[id]`` in the
  residual stream and put the input token first at every position whatever
  the layers computed, and no precision could be told from another; the
  convolution's taps Normal(0, 0.3) (a fan-in of 4), its bias Normal(0, 0.1);
  norm weights 1 + Normal(0, 0.1), so that none can be left out unnoticed;
  ``A_log = log U(1, 16)`` and ``dt_bias = softplus^-1(dt)``, ``dt``
  log-uniform over [0.001, 0.1] (the Mamba-2 initialisation): heads whose
  state forgets within a few tokens and heads that carry it for thousands;
  ``D`` ones.

``precision``: ``"float32"`` — the reference: bfloat16 weights upcast one
matrix at a time, everything float32, products under
``default_matmul_precision("highest")``; ``"bfloat16"`` — as the configuration
states it: activations and both operands of every product in bfloat16,
accumulated in float32; norms, softmax, the gates ``dt`` and ``a`` and the
state in float32; ``"float8"`` — as bfloat16 with both operands of every
product (keys, values and the recurrence's x, B, C among them) rounded to
``float8_e4m3fn`` under a per-tensor scale.

Queries are taken 512 at a time; the head is one product.
"""
import functools
import math
import zlib

import jax
import jax.numpy as jnp

from reference.afmoe import _rms, param_dtype
from reference.gpt2 import _fp8, seed_key
from reference.smallthinker import _blocks


def _dims(cfg):
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    inner = H * P
    return H, P, N, inner, inner + 2 * N


def layer_shapes(cfg, i):
    d, f = cfg["hidden_size"], cfg["shared_intermediate_size"]
    s = {"input_layernorm": (d,), "post_attention_layernorm": (d,),
         "mlp_gate": (d, f), "mlp_up": (d, f), "mlp_down": (f, d)}
    if cfg["layer_types"][i] == "mamba":
        H, _, _, inner, conv = _dims(cfg)
        s.update(in_proj=(d, inner + conv + H),
                 conv1d=(cfg["mamba_d_conv"], conv), conv_bias=(conv,),
                 dt_bias=(H,), A_log=(H,), D=(H,), norm=(inner,),
                 out_proj=(inner, d))
    else:
        D = d // cfg["num_attention_heads"]
        hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        s.update(q_proj=(d, hq * D), k_proj=(d, hkv * D),
                 v_proj=(d, hkv * D), o_proj=(hq * D, d))
    return s


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _leaf(key, shape, dt, kind, std):
    if kind == "A_log":
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif kind == "dt_bias":
        dt_ = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                         math.log(1e-3), math.log(1e-1)))
        x = dt_ + jnp.log(-jnp.expm1(-dt_))          # softplus^-1
    elif kind == "D":
        x = jnp.ones(shape, jnp.float32)
    else:
        mean = 1.0 if kind == "norm" else 0.0
        x = mean + std * jax.random.normal(key, shape, jnp.float32)
    return x.astype(jnp.bfloat16).astype(dt)


def init_params(cfg, seed):
    """``{"embed_tokens", "norm", "layers": [{name: array}]}`` on the device —
    one dict a published layer, no ``lm_head`` (the head is the embedding) —
    all kept in the deployment's ``param_dtype`` (``as_found`` above says
    which leaf is drawn how).  A leaf's key is the seed's folded with its
    path; one compiled maker a shape and kind."""
    dt = param_dtype(cfg)
    key = seed_key(seed)

    def leaf(path, shape):
        name = path.rsplit(".", 1)[-1]
        if name in ("A_log", "dt_bias", "D"):
            kind, std = name, 0.0
        elif name == "embed_tokens":
            kind, std = "matrix", 0.02 / cfg["embedding_multiplier"]
        elif name == "conv1d":
            kind, std = "matrix", 0.3
        elif name == "conv_bias":
            kind, std = "matrix", 0.1
        elif len(shape) == 1:
            kind, std = "norm", 0.1
        else:
            kind, std = "matrix", 0.02
        return _leaf(jax.random.fold_in(key, zlib.crc32(path.encode())),
                     tuple(shape), dt, kind, std)

    d, V = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed_tokens": leaf("embed_tokens", (V, d)),
            "norm": leaf("norm", (d,)),
            "layers": [{name: leaf(f"layers.{i}.{name}", shape)
                        for name, shape in layer_shapes(cfg, i).items()}
                       for i in range(cfg["num_hidden_layers"])]}


def _forward(params, tokens, cfg, dt, low_matmul):
    """tokens (T,) int32 -> float32 logits (T, V), causal."""
    q8 = _fp8 if low_matmul else (lambda x: x)

    def mm(x, w):                       # a product at the precision
        return jnp.dot(q8(x.astype(dt)), q8(w.astype(dt)),
                       preferred_element_type=jnp.float32)

    T = tokens.shape[0]
    d = cfg["hidden_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D, G = d // hq, hq // hkv
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    H, P, N, inner, conv = _dims(cfg)
    K = cfg["mamba_d_conv"]
    pos = jnp.arange(T, dtype=jnp.int32)
    h = (params["embed_tokens"][tokens].astype(jnp.float32)
         * cfg["embedding_multiplier"]).astype(dt)

    def attention(x, p):
        q = mm(x, p["q_proj"]).astype(dt).reshape(T, hq, D)
        k, v = (q8(mm(x, p[n]).astype(dt).reshape(T, hkv, D))
                for n in ("k_proj", "v_proj"))

        def rows(qb, i0):
            qg = q8(qb).reshape(-1, hkv, G, D)
            s = jnp.einsum("qkgd,tkd->kgqt", qg, k,
                           preferred_element_type=jnp.float32) \
                * cfg["attention_multiplier"]
            qi = (i0 + jnp.arange(qb.shape[0]))[:, None]
            w = jax.nn.softmax(
                jnp.where((pos[None, :] <= qi)[None, None], s, -1e30), -1)
            o = jnp.einsum("kgqt,tkd->qkgd", q8(w.astype(dt)), v,
                           preferred_element_type=jnp.float32)
            return o.reshape(-1, hq * D).astype(dt)

        return mm(_blocks(rows, q, 512), p["o_proj"])

    def mamba(x, p):
        zxd = mm(x, p["in_proj"])                           # float32
        z = zxd[:, :inner].astype(dt)
        step = jax.nn.softplus(zxd[:, inner + conv:]
                               + p["dt_bias"].astype(jnp.float32))  # (T, H)
        decay = jnp.exp(-jnp.exp(p["A_log"].astype(jnp.float32)) * step)
        # the convolution: position t reads inputs t - 3 .. t, zeros before 0
        seq = jnp.pad(zxd[:, inner:inner + conv].astype(dt).astype(
            jnp.float32), ((K - 1, 0), (0, 0)))
        taps = p["conv1d"].astype(jnp.float32)
        xbc = jax.nn.silu(
            sum(seq[j:j + T] * taps[j] for j in range(K))
            + p["conv_bias"].astype(jnp.float32)).astype(dt)
        xs, Bm, Cm = (q8(a.astype(jnp.float32)) for a in (
            xbc[:, :inner].reshape(T, H, P), xbc[:, inner:inner + N],
            xbc[:, inner + N:]))
        skip = p["D"].astype(jnp.float32)[:, None]

        def token(S, t):
            xt, Bt, Ct, st, at = t
            S = at[:, None, None] * S \
                + (st[:, None] * xt)[:, :, None] * Bt[None, None, :]
            return S, jnp.einsum("hpn,n->hp", S, Ct) + skip * xt

        with jax.default_matmul_precision("highest"):   # the state is float32
            _, y = jax.lax.scan(token, jnp.zeros((H, P, N), jnp.float32),
                                (xs, Bm, Cm, step, decay))
        y = y.reshape(T, inner) * jax.nn.silu(z.astype(jnp.float32))
        return mm(_rms(y, p["norm"], eps), p["out_proj"])

    for kind, p in zip(cfg["layer_types"], params["layers"]):
        x = _rms(h, p["input_layernorm"], eps)
        mix = (mamba if kind == "mamba" else attention)(x, p)
        h = h + (res * mix).astype(dt)
        y = _rms(h, p["post_attention_layernorm"], eps)
        mid = (jax.nn.silu(mm(y, p["mlp_gate"])) * mm(y, p["mlp_up"])
               ).astype(dt)
        h = h + (res * mm(mid, p["mlp_down"])).astype(dt)
    x = _rms(h, params["norm"], eps)
    logits = jax.lax.dot_general(
        q8(x.astype(dt)), q8(params["embed_tokens"].astype(dt)),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    return logits / cfg["logits_scaling"]


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
