"""The served Granite 4.0-H decoder as `mxtpu-serve` would build it:
``models.granite_hybrid.GraniteHybridModel`` -> ``GenerationEngine`` (paged
blocks for the four attention layers, state rows and snapshot rows for the
runs of Mamba-2 layers, prefix cache, bursts) -> ``ModelServer.add_model`` ->
``preload()`` -> ``start()``.

The weights are the reference's (made from the seed by
``reference/granite_hybrid.init_params``: one dict a published layer).  The
program serves a run of consecutive Mamba layers as one layer whose parameters
are stacked (and W_in held as its three groups of columns), so
``load_weights`` stacks each run a leaf at a time and lets go of the forty as
it goes (6.4 GB cannot be held twice); everything else is
ADOPTED and not copied, and dropped before the reference runs
(`qwen3next_serve` does the same, and is reused for it).
"""
from programs import qwen3next_serve

#: the source's keys that ``GraniteHybridModel`` takes under the same name
MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "layer_types",
    "num_attention_heads", "num_key_value_heads", "shared_intermediate_size",
    "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv",
    "mamba_n_groups", "mamba_expand", "mamba_chunk_size", "mamba_conv_bias",
    "mamba_proj_bias", "attention_bias", "attention_multiplier",
    "embedding_multiplier", "residual_multiplier", "logits_scaling",
    "num_local_experts", "position_embedding_type", "tie_word_embeddings",
    "rms_norm_eps", "max_position_embeddings")


def build_net(cfg):
    """The net with no parameter allocated (``load_weights`` adopts them)."""
    from incubator_mxnet_tpu.models.granite_hybrid import GraniteHybridModel
    return GraniteHybridModel(**{k: cfg[k] for k in MODEL_KEYS},
                              dtype=cfg["deployment"]["param_dtype"])


def load_weights(net, ref_params):
    """Adopt the reference's arrays, each run of Mamba layers stacked on a
    leading axis (the reference's own dicts are emptied as their leaves
    go)."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.models.granite_hybrid import layer_runs
    layers, served, at = ref_params["layers"], [], 0
    for (kind, n), layer in zip(layer_runs(net.layer_types), net.layers):
        group, at = layers[at:at + n], at + n
        if kind == "attention":
            served.append(group[0])
            continue
        run = {name: jnp.stack([g.pop(name) for g in group])
               for name in list(group[0])}
        # W_in [z | xBC | dt] is held as its three groups of columns
        w_in, col = run.pop("in_proj"), 0
        for part in ("z", "xbc", "dt"):
            width = getattr(layer, "in_proj_" + part).shape[-1]
            run["in_proj_" + part] = w_in[..., col:col + width]
            col += width
        served.append(run)
    net.adopt_arrays({"embed_tokens": ref_params["embed_tokens"],
                      "norm": ref_params["norm"], "layers": served})


build_server = qwen3next_serve.build_server     # the state store's settings
served_state = qwen3next_serve.served_state     # and its rows' type
stop_server = qwen3next_serve.stop_server
