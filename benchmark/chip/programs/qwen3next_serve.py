"""The served Qwen3-Next decoder as `mxtpu-serve` would build it:
``models.qwen3_next.Qwen3NextModel`` -> ``GenerationEngine`` (paged blocks for
the full-attention layers, state rows and snapshot rows for the Gated DeltaNet
layers, prefix cache, bursts) -> ``ModelServer.add_model`` -> ``preload()`` ->
``start()``.

The weights are the reference's (made from the seed by
``reference/qwen3next.init_params``), in the layout both sides share, ADOPTED
and not copied, and dropped before the reference runs (`afmoe_serve` does the
same, and is reused for it).
"""
from programs import afmoe_serve, gpt_serve

#: the source's keys that ``Qwen3NextModel`` takes under the same name
MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "linear_num_key_heads",
    "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim",
    "linear_conv_kernel_dim", "full_attention_interval",
    "partial_rotary_factor", "moe_intermediate_size",
    "shared_expert_intermediate_size", "num_experts", "num_experts_per_tok",
    "norm_topk_prob", "decoder_sparse_step", "mlp_only_layers", "rope_theta",
    "rms_norm_eps", "max_position_embeddings")


def build_net(cfg):
    """The net with no parameter allocated (``load_weights`` adopts them)."""
    from incubator_mxnet_tpu.models.qwen3_next import Qwen3NextModel
    return Qwen3NextModel(
        **{k: cfg[k] for k in MODEL_KEYS},
        num_experts_published=cfg.get("num_experts_published"),
        first_expert=cfg.get("first_expert", 0),
        dtype=cfg["deployment"]["param_dtype"])


load_weights = afmoe_serve.load_weights


def build_server(cfg, net, port, host="127.0.0.1"):
    """Engine + server, warm and listening, as `gpt_serve` builds them, with
    the deployment's two settings of the state store."""
    from incubator_mxnet_tpu.serving import GenerationEngine, ModelServer
    dep = cfg["deployment"]
    engine = GenerationEngine(
        net, name=dep["model_name"], max_slots=dep["max_slots"],
        max_len=dep["max_len"], prefill_buckets=dep["prefill_buckets"],
        paged=dep["paged"], block_size=dep["block_size"],
        num_blocks=dep.get("num_blocks"), prefix_cache=dep["prefix_cache"],
        scan_steps=dep["scan_steps"], logprobs_topn=dep["logprobs_topn"],
        state_snapshot_tokens=dep["state_snapshot_tokens"],
        state_snapshot_rows=dep["state_snapshot_rows"])
    srv = ModelServer(port=port, host=host)
    srv.add_model(dep["model_name"], engine)
    srv.preload()
    srv.start()
    afmoe_serve._SERVED.append((net, engine))
    return srv, engine


def served_state(net, engine):
    """`gpt_serve.served_state`, and the type of the state rows beside the
    block pool's, each read off the arrays."""
    out = gpt_serve.served_state(net, engine)
    kinds = {(str(a.dtype), int(a.dtype.itemsize)) for a in engine._recur}
    if len(kinds) != 1:
        raise ValueError(f"the state rows are of types {sorted(kinds)}")
    out["state_dtype"], out["state_bytes"] = kinds.pop()
    return out


def stop_server(srv):
    """`afmoe_serve.stop_server`, and the state rows go with the pools."""
    engines = [e for _, e in afmoe_serve._SERVED]
    afmoe_serve.stop_server(srv)
    for engine in engines:
        engine._recur = ()
