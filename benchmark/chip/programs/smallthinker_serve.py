"""The served SmallThinker decoder as `mxtpu-serve` would build it:
``models.smallthinker.SmallThinkerModel`` -> ``GenerationEngine`` (paged,
prefix cache, bursts) -> ``ModelServer.add_model`` -> ``preload()`` ->
``start()``.

The weights are the reference's (made from the seed by
``reference/smallthinker.init_params``), in the layout both sides share,
ADOPTED and not copied, and dropped before the reference runs: 7.9 GB cannot
be held twice on a 16 GB chip (`afmoe_serve` does the same, and is reused for
it).
"""
from programs import afmoe_serve

#: the source's keys that ``SmallThinkerModel`` takes under the same name
MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "moe_num_primary_experts",
    "moe_num_active_primary_experts", "moe_ffn_hidden_size", "rope_layout",
    "sliding_window_layout", "sliding_window_size",
    "moe_primary_router_apply_softmax", "norm_topk_prob", "rope_theta",
    "rms_norm_eps", "max_position_embeddings")


def build_net(cfg):
    """The net with no parameter allocated (``load_weights`` adopts them)."""
    from incubator_mxnet_tpu.models.smallthinker import SmallThinkerModel
    return SmallThinkerModel(
        **{k: cfg[k] for k in MODEL_KEYS},
        moe_num_primary_experts_published=cfg.get(
            "moe_num_primary_experts_published"),
        first_expert=cfg.get("first_expert", 0),
        dtype=cfg["deployment"]["param_dtype"])


load_weights = afmoe_serve.load_weights
build_server = afmoe_serve.build_server
served_state = afmoe_serve.served_state
stop_server = afmoe_serve.stop_server
