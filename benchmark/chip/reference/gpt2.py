"""Plain GPT-2 forward, written from the published description (Radford et al.
2019; the `openai-community/gpt2*` config.json keys) in straightforward
``jax.numpy``.  Imports nothing of the program and takes nothing the program
made: weights come from :func:`init_params` and the seed.

Layout is the source's own: ``Conv1D`` matrices are ``(in, out)``.  Layers are
stacked on a leading axis and the forward scans over them, so it compiles in
seconds at any depth.

``precision``:

* ``"float32"`` — float32 everywhere, matmuls under
  ``default_matmul_precision("highest")``: the reference;
* ``"bfloat16"`` — parameters, activations and every intermediate in
  bfloat16;
* ``"float8"`` — as bfloat16, with both operands of every matrix product (the
  cached keys and values among them) rounded to ``float8_e4m3fn`` under a
  per-tensor scale.
"""
import math

import jax
import jax.numpy as jnp


def seed_key(seed):
    """A PRNG key from any whole number up to and beyond 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _shapes(cfg):
    d, L, V, P = (cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"],
                  cfg["n_positions"])
    f = cfg.get("n_inner") or 4 * d
    return {
        "wte": (V, d), "wpe": (P, d),
        "wq": (L, d, d), "wk": (L, d, d), "wv": (L, d, d), "wo": (L, d, d),
        "bq": (L, d), "bk": (L, d), "bv": (L, d), "bo": (L, d),
        "wfc": (L, d, f), "bfc": (L, f), "wproj": (L, f, d), "bproj": (L, d),
    }


def init_params(cfg, seed):
    """Every weight from the seed, on the device, in one jitted call:
    Normal(0, initializer_range) for matrices, embeddings and biases;
    LayerNorm gain 1 and bias 0."""
    shapes = _shapes(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    d, L = cfg["n_embd"], cfg["n_layer"]

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            out[name] = std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
        for name in ("ln1", "ln2"):
            out[name + "_g"] = jnp.ones((L, d), jnp.float32)
            out[name + "_b"] = jnp.zeros((L, d), jnp.float32)
        out["lnf_g"] = jnp.ones((d,), jnp.float32)
        out["lnf_b"] = jnp.zeros((d,), jnp.float32)
        return out

    return jax.jit(make)(seed_key(seed))


def gelu_new(x):
    """The source's ``gelu_new``: the tanh form."""
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x * x * x)))


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _fp8(x):
    """Round to float8_e4m3fn under a per-tensor scale; keep x's type."""
    scale = jnp.max(jnp.abs(x)).astype(jnp.float32) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (x.astype(jnp.float32) / scale).astype(jnp.float8_e4m3fn)
    return (q.astype(jnp.float32) * scale).astype(x.dtype)


def _forward(params, tokens, cfg, low_matmul=False):
    """tokens (B, T) int32 -> logits (B, T, V), causal."""
    q8 = _fp8 if low_matmul else (lambda x: x)
    H = cfg["n_head"]
    eps = float(cfg.get("layer_norm_epsilon", 1e-5))
    B, T = tokens.shape
    dt = params["wte"].dtype
    h = params["wte"][tokens] + params["wpe"][jnp.arange(T)][None]
    causal = jnp.tril(jnp.ones((T, T), bool))
    layer_keys = ("ln1_g", "ln1_b", "wq", "wk", "wv", "wo", "bq", "bk",
                  "bv", "bo", "ln2_g", "ln2_b", "wfc", "bfc", "wproj",
                  "bproj")

    def split(x):
        return x.reshape(B, T, H, -1).transpose(0, 2, 1, 3)

    def layer(h, p):
        a = _layer_norm(h, p["ln1_g"], p["ln1_b"], eps)
        q, k, v = (split(q8(a) @ q8(p["w" + n]) + p["b" + n]) for n in "qkv")
        s = jnp.einsum("bhqd,bhkd->bhqk", q8(q), q8(k)) \
            / math.sqrt(q.shape[-1])
        s = jnp.where(causal[None, None], s, jnp.asarray(-1e30, s.dtype))
        w = jax.nn.softmax(s, axis=-1).astype(dt)
        o = jnp.einsum("bhqk,bhkd->bhqd", q8(w), q8(v))
        o = o.transpose(0, 2, 1, 3).reshape(B, T, -1)
        h = h + q8(o) @ q8(p["wo"]) + p["bo"]
        m = _layer_norm(h, p["ln2_g"], p["ln2_b"], eps)
        f = gelu_new(q8(m) @ q8(p["wfc"]) + p["bfc"])
        h = h + q8(f) @ q8(p["wproj"]) + p["bproj"]
        return h.astype(dt), None

    h, _ = jax.lax.scan(layer, h, {k: params[k] for k in layer_keys})
    h = _layer_norm(h, params["lnf_g"], params["lnf_b"], eps)
    return q8(h) @ q8(params["wte"]).T


def make_forward(cfg, precision="float32"):
    """A jitted ``(params, tokens) -> float32 logits`` at ``precision``."""
    if precision == "float32":
        def fwd(params, tokens):
            with jax.default_matmul_precision("highest"):
                return _forward(params, tokens, cfg)
    elif precision in ("bfloat16", "float8"):
        def fwd(params, tokens):
            low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
            return _forward(low, tokens, cfg,
                            precision == "float8").astype(jnp.float32)
    else:
        raise ValueError(f"no such precision: {precision!r}")
    return jax.jit(fwd)
