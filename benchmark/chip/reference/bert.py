"""Plain BERT pretraining step (Devlin et al. 2018, arXiv:1810.04805; the
`google-bert/bert-*` config.json keys): post-LN encoder, pooler, MLM and NSP
heads, the summed loss, its gradients and Adam, in straightforward
``jax.numpy``.  Imports nothing of the program; weights and batches come from
the seed.

Where the job as the program trains it departs from the paper (see the
configuration's ``as_found``) this follows the job, because the parameters
differ: an untied MLM decoder with its own bias, and an MLM target at every
position.  LayerNorm's epsilon is the source's.

Matrices are ``(in, out)``; layers are stacked on a leading axis and scanned,
each layer rematerialised in the backward pass so a whole batch fits beside
nothing else.

``precision``:

* ``"float32"`` — float32, matmuls at ``highest``: the reference;
* ``"bfloat16"`` — parameters and activations bfloat16 (what the configuration
  states; used by tests);
* ``"float8"`` — as bfloat16, with both operands of every matrix product
  rounded to ``float8_e4m3fn`` under a per-tensor scale: the control, the next
  precision below bfloat16.
"""
import math

import jax
import jax.numpy as jnp

from .gpt2 import _fp8, _layer_norm, seed_key

LAYER_KEYS = ("wq", "wk", "wv", "wo", "bq", "bk", "bv", "bo", "ln1_g",
              "ln1_b", "wfc", "bfc", "wproj", "bproj", "ln2_g", "ln2_b")


def _shapes(cfg):
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    f, P, S = (cfg["intermediate_size"], cfg["max_position_embeddings"],
               cfg["type_vocab_size"])
    return {
        "word_emb": (V, d), "type_emb": (S, d), "pos_emb": (P, d),
        "wq": (L, d, d), "wk": (L, d, d), "wv": (L, d, d), "wo": (L, d, d),
        "bq": (L, d), "bk": (L, d), "bv": (L, d), "bo": (L, d),
        "wfc": (L, d, f), "bfc": (L, f), "wproj": (L, f, d), "bproj": (L, d),
        "pool_w": (d, d), "pool_b": (d,),
        "mlm_w": (d, d), "mlm_b": (d,),
        "dec_w": (d, V), "dec_b": (V,),
        "nsp_w": (d, 2), "nsp_b": (2,),
    }


def _ln_names(cfg):
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    return {"emb_ln": (d,), "ln1": (L, d), "ln2": (L, d), "mlm_ln": (d,)}


def init_params(cfg, seed):
    """Every weight from the seed, on the device, in one jitted call, in the
    type the job states: Normal(0, initializer_range) for matrices, embeddings
    and biases; LayerNorm gain 1 and bias 0."""
    dtype = jnp.dtype(cfg["job"]["param_dtype"])
    shapes = _shapes(cfg)
    std = float(cfg.get("initializer_range", 0.02))

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            out[name] = (std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)).astype(dtype)
        for name, shape in _ln_names(cfg).items():
            out[name + "_g"] = jnp.ones(shape, dtype)
            out[name + "_b"] = jnp.zeros(shape, dtype)
        return out

    return jax.jit(make)(seed_key(seed))


def _gelu(x):
    """The source's ``gelu``: the erf form."""
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def _log_softmax(x):
    x = x.astype(jnp.float32)
    return x - jax.nn.logsumexp(x, axis=-1, keepdims=True)


def loss_fn(params, ids, types, labels, cfg, low_matmul=False):
    """MLM + NSP loss of one batch.  ids, types (B, T) int32; labels
    (B, T + 1): T MLM targets and the NSP class."""
    H = cfg["num_attention_heads"]
    eps = float(cfg.get("layer_norm_eps", 1e-12))
    B, T = ids.shape
    q8 = _fp8 if low_matmul else (lambda x: x)

    def mm(x, w):
        return q8(x) @ q8(w)

    def split(x):
        return x.reshape(B, T, H, -1).transpose(0, 2, 1, 3)

    h = (params["word_emb"][ids] + params["type_emb"][types]
         + params["pos_emb"][:T][None])
    h = _layer_norm(h, params["emb_ln_g"], params["emb_ln_b"], eps)
    dt = h.dtype

    @jax.checkpoint
    def layer(h, p):
        q, k, v = (split(mm(h, p["w" + n]) + p["b" + n]) for n in "qkv")
        s = jnp.einsum("bhqd,bhkd->bhqk", q8(q), q8(k)) \
            / math.sqrt(q.shape[-1])
        w = jax.nn.softmax(s, axis=-1).astype(dt)
        o = jnp.einsum("bhqk,bhkd->bhqd", q8(w), q8(v))
        o = o.transpose(0, 2, 1, 3).reshape(B, T, -1)
        h = _layer_norm(h + mm(o, p["wo"]) + p["bo"],
                        p["ln1_g"], p["ln1_b"], eps).astype(dt)
        f = mm(_gelu(mm(h, p["wfc"]) + p["bfc"]), p["wproj"]) + p["bproj"]
        return _layer_norm(h + f, p["ln2_g"], p["ln2_b"], eps).astype(dt)

    h, _ = jax.lax.scan(lambda c, p: (layer(c, p), None), h,
                        {k: params[k] for k in LAYER_KEYS})
    pooled = jnp.tanh(mm(h[:, 0], params["pool_w"]) + params["pool_b"])
    nsp = mm(pooled, params["nsp_w"]) + params["nsp_b"]
    m = _gelu(mm(h, params["mlm_w"]) + params["mlm_b"])
    m = _layer_norm(m, params["mlm_ln_g"], params["mlm_ln_b"], eps)
    mlm = mm(m.astype(dt), params["dec_w"]) + params["dec_b"]
    mlm_t = labels[:, :T].astype(jnp.int32)
    nsp_t = labels[:, T].astype(jnp.int32)
    mlm_ll = jnp.take_along_axis(_log_softmax(mlm), mlm_t[..., None], -1)
    nsp_ll = jnp.take_along_axis(_log_softmax(nsp), nsp_t[:, None], -1)
    return -jnp.mean(mlm_ll) - jnp.mean(nsp_ll)


def adam_update(params, grads, m, v, step, hp):
    """Adam as the job states it: bias correction folded into the rate,
    no weight decay."""
    b1, b2 = hp.get("beta1", 0.9), hp.get("beta2", 0.999)
    eps, lr = hp.get("epsilon", 1e-8), hp["learning_rate"]
    t = jnp.asarray(step, jnp.float32)
    rate = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    params = jax.tree.map(
        lambda w, a, b: w - rate * a / (jnp.sqrt(b) + eps), params, m, v)
    return params, m, v


def make_train_step(cfg, hp, precision="float32"):
    """A jitted ``(params, m, v, step, ids, types, labels) ->
    (loss, grads, params', m', v')``.  State is float32 in every precision;
    the forward and backward run at ``precision``."""
    def step_fn(params, m, v, step, ids, types, labels):
        if precision == "float32":
            with jax.default_matmul_precision("highest"):
                loss, grads = jax.value_and_grad(loss_fn)(
                    params, ids, types, labels, cfg)
        elif precision in ("bfloat16", "float8"):
            low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
            loss, grads = jax.value_and_grad(loss_fn)(
                low, ids, types, labels, cfg, precision == "float8")
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        else:
            raise ValueError(f"no such precision: {precision!r}")
        new_p, new_m, new_v = adam_update(params, grads, m, v, step, hp)
        return loss.astype(jnp.float32), grads, new_p, new_m, new_v
    return jax.jit(step_fn, donate_argnums=(0, 1, 2))


def follow(cfg, hp, seed, batches, precision="float32"):
    """Train ``len(batches)`` steps from the seed's weights.  Returns the
    losses, the per-leaf norm of the first gradient and the per-leaf norm of
    the parameters' change over all the steps, keyed by leaf name, and under
    ``"rms_leaves"`` the leaves whose gradient gaps `checks.train_numbers`
    takes the root mean square of: the query and key matrices.  Their
    gradients come through the softmax of the attention scores, the longest
    chain of matrix products in the step, and their relative error does not
    ride on the size of the seed's gradient, which the value, output and FFN
    matrices' does (PERF.md, PR 23)."""
    init = init_params(cfg, seed)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), init)
    start = jax.tree.map(jnp.copy, params)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    step_fn = make_train_step(cfg, hp, precision)
    norms = jax.jit(leaf_norms)
    losses, grad_norms = [], None
    for i, (ids, types, labels) in enumerate(batches, 1):
        loss, grads, params, m, v = step_fn(params, m, v, i, ids, types,
                                            labels)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = flat_norms(norms(grads))
        del grads
    delta = jax.tree.map(lambda a, b: a - b, params, start)
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": flat_norms(norms(delta)),
            "rms_leaves": sorted(k for k in grad_norms
                                 if k.partition(".")[0] in ("wq", "wk"))}


def leaf_norms(tree):
    """Norm of every leaf as the program holds them: a stacked array gives
    one norm per layer."""
    out = {}
    for name, a in tree.items():
        a = a.astype(jnp.float32)
        axes = tuple(range(1, a.ndim)) if name in LAYER_KEYS else None
        out[name] = jnp.sqrt(jnp.sum(jnp.square(a), axis=axes))
    return out


def flat_norms(norms):
    """``{"wq": (L,), "pool_w": ()}`` -> ``{"wq.0": x, ..., "pool_w": y}``."""
    out = {}
    for name, val in norms.items():
        if val.ndim:
            for i, x in enumerate(val):
                out[f"{name}.{i}"] = float(x)
        else:
            out[name] = float(val)
    return out
