"""The served dots3-note decoder as `mxtpu-serve` would build it:
``models.dots3.Dots3Model`` -> ``GenerationEngine`` (paged latent cache, prefix
cache, bursts, a miss longer than the largest bucket in chunks) ->
``ModelServer.add_model`` -> ``preload()`` -> ``start()``.

The weights are the reference's (made from the seed by
``reference/dots3.init_params``), in the layout both sides share, ADOPTED and
not copied: 7.3 GB cannot be held twice on a 16 GB chip.
"""
from programs import afmoe_serve, gpt_serve

#: the source's keys that ``Dots3Model`` takes under the same name
MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "first_k_dense_replace", "layer_types",
    "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "rope_theta", "swa_num_attention_heads",
    "swa_q_lora_rank", "swa_kv_lora_rank", "swa_qk_nope_head_dim",
    "swa_qk_rope_head_dim", "swa_v_head_dim", "swa_rope_theta",
    "sliding_window_size", "index_n_heads", "index_head_dim", "index_topk",
    "n_routed_experts", "num_experts_per_tok", "n_shared_experts",
    "norm_topk_prob", "routed_scaling_factor", "apply_mla_qkv_lora_rescale",
    "rms_norm_eps", "max_position_embeddings")


def build_net(cfg):
    """The net with no parameter allocated (``load_weights`` adopts them)."""
    from incubator_mxnet_tpu.models.dots3 import Dots3Model
    return Dots3Model(
        **{k: cfg[k] for k in MODEL_KEYS},
        n_routed_experts_published=cfg.get("n_routed_experts_published"),
        first_expert=cfg.get("first_expert", 0),
        dtype=cfg["deployment"]["param_dtype"])


load_weights = afmoe_serve.load_weights
build_server = afmoe_serve.build_server     # and remembers what to let go of
served_state = gpt_serve.served_state       # the same reading of the arrays
stop_server = afmoe_serve.stop_server
