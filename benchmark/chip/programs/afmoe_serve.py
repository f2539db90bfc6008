"""The served AFMoE decoder as `mxtpu-serve` would build it:
``models.afmoe.AFMoEModel`` -> ``GenerationEngine`` (paged, prefix cache,
bursts) -> ``ModelServer.add_model`` -> ``preload()`` -> ``start()``.

The weights are the reference's (made from the seed by
``reference/afmoe.init_params``), in the layout both sides share, ADOPTED and
not copied: 8.1 GB cannot be held twice on a 16 GB chip, so the net is built
without allocating and takes the reference's device arrays as its own.
"""
from programs import gpt_serve

#: what ``build_server`` built, for ``stop_server`` to let go of
_SERVED = []

#: the source's keys that ``AFMoEModel`` takes under the same name
MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "num_dense_layers", "layer_types",
    "num_attention_heads", "num_key_value_heads", "head_dim", "num_experts",
    "num_experts_per_tok", "num_shared_experts", "sliding_window",
    "rope_theta", "rms_norm_eps", "route_norm", "route_scale", "mup_enabled",
    "max_position_embeddings")


def build_net(cfg):
    """The net with no parameter allocated (``load_weights`` adopts them)."""
    from incubator_mxnet_tpu.models.afmoe import AFMoEModel
    return AFMoEModel(
        **{k: cfg[k] for k in MODEL_KEYS},
        num_experts_published=cfg.get("num_experts_published"),
        first_expert=cfg.get("first_expert", 0),
        dtype=cfg["deployment"]["param_dtype"])


def load_weights(net, ref_params):
    net.adopt_arrays(ref_params)


def build_server(cfg, net, port, host="127.0.0.1"):
    """Engine + server, warm and listening, as `gpt_serve` builds them."""
    srv, engine = gpt_serve.build_server(cfg, net, port, host)
    _SERVED.append((net, engine))
    return srv, engine


served_state = gpt_serve.served_state       # the same reading of the arrays


def stop_server(srv):
    """The SIGTERM drain sequence, without the signal — and then the device
    arrays themselves are dropped, not left to the collector: the reference
    that runs next makes its own 8.1 GB of weights, and two sets do not fit."""
    srv.shutdown(drain_seconds=5.0)
    while _SERVED:
        net, engine = _SERVED.pop()
        engine._cache = ()
        for p in net.collect_params().values():
            p._data = None
