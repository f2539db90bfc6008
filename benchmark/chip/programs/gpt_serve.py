"""The served decoder as `mxtpu-serve` builds it (`_cli.serve_main`):
``models.gpt.GPTModel`` -> ``GenerationEngine`` -> ``ModelServer.add_model``
-> ``preload()`` -> ``start()``.  `serve_main` has no flag for the prefill
buckets or the pool size, so this adapter makes the same calls with the
configuration's ``deployment`` settings.

The weights are the reference's (made from the seed by
``reference/<name>.init_params``) moved into the program's layout; the program
makes none of its own.
"""
import jax
import numpy as np


def model_kwargs(cfg):
    """The source's config.json keys -> ``GPTModel`` arguments."""
    d = cfg["n_embd"]
    return dict(vocab_size=cfg["vocab_size"], units=d,
                hidden_size=cfg.get("n_inner") or 4 * d,
                num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
                max_length=cfg["n_positions"], dropout=0.0)


def build_net(cfg):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.models.gpt import GPTModel
    net = GPTModel(**model_kwargs(cfg))
    net.initialize(mx.init.Zero())
    with mx.autograd.pause():
        net(mx.nd.array(np.zeros((1, 2), np.int32)))   # settle shapes
    return net


@jax.jit
def _to_program_layout(ref):
    """Reference (stacked, ``(in, out)``) -> per-layer ``Dense`` leaves
    ``(out, in)``, in one call."""
    L = ref["wq"].shape[0]
    layers = []
    for i in range(L):
        layers.append({
            "ln1": (ref["ln1_g"][i], ref["ln1_b"][i]),
            "ln2": (ref["ln2_g"][i], ref["ln2_b"][i]),
            "query": (ref["wq"][i].T, ref["bq"][i]),
            "key": (ref["wk"][i].T, ref["bk"][i]),
            "value": (ref["wv"][i].T, ref["bv"][i]),
            "proj": (ref["wo"][i].T, ref["bo"][i]),
            "ffn_1": (ref["wfc"][i].T, ref["bfc"][i]),
            "ffn_2": (ref["wproj"][i].T, ref["bproj"][i]),
        })
    return {"wte": ref["wte"], "wpe": ref["wpe"], "layers": layers,
            "ln_f": (ref["lnf_g"], ref["lnf_b"])}


def load_weights(net, ref_params):
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray
    w = _to_program_layout(ref_params)

    def put(param, value):
        if tuple(param.shape) != tuple(value.shape):
            raise ValueError(f"{param.name}: program has {param.shape}, "
                             f"reference gives {value.shape}")
        param.set_data(NDArray(value))

    put(net.embed.weight, w["wte"])
    put(net.pos_embed.weight, w["wpe"])
    for cell, lw in zip(net.cells._children.values(), w["layers"]):
        for ln in ("ln1", "ln2"):
            put(getattr(cell, ln).gamma, lw[ln][0])
            put(getattr(cell, ln).beta, lw[ln][1])
        for name in ("query", "key", "value", "proj"):
            dense = getattr(cell.attention, name)
            put(dense.weight, lw[name][0])
            put(dense.bias, lw[name][1])
        for name in ("ffn_1", "ffn_2"):
            dense = getattr(cell.ffn, name)
            put(dense.weight, lw[name][0])
            put(dense.bias, lw[name][1])
    put(net.ln_f.gamma, w["ln_f"][0])
    put(net.ln_f.beta, w["ln_f"][1])


def build_server(cfg, net, port, host="127.0.0.1"):
    """Engine + server, warm (every program compiled or loaded) and
    listening.  Returns ``(server, engine)``."""
    from incubator_mxnet_tpu.serving import GenerationEngine, ModelServer
    dep = cfg["deployment"]
    engine = GenerationEngine(
        net, name=dep["model_name"], max_slots=dep["max_slots"],
        max_len=dep["max_len"], prefill_buckets=dep["prefill_buckets"],
        paged=dep["paged"], block_size=dep["block_size"],
        num_blocks=dep.get("num_blocks"), prefix_cache=dep["prefix_cache"],
        scan_steps=dep["scan_steps"], logprobs_topn=dep["logprobs_topn"])
    srv = ModelServer(port=port, host=host)
    srv.add_model(dep["model_name"], engine)
    srv.preload()
    srv.start()
    return srv, engine


def served_state(net, engine):
    """What is served, read from the arrays themselves: the type and width of
    the parameters and of the KV pool, and jax's matmul precision in this
    process.  The runner holds these against the configuration's
    ``deployment`` and feeds the widths to the roofline."""
    def one(arrays, what):
        kinds = {str(a.dtype) for a in arrays}
        if len(kinds) != 1:
            raise ValueError(f"{what} are of mixed types: {sorted(kinds)}")
        return kinds.pop(), int(arrays[0].dtype.itemsize)

    param_dtype, param_bytes = one(
        [p.data()._data for p in net.collect_params().values()],
        "the parameters")
    kv_dtype, kv_bytes = one(list(engine._cache), "the KV pool's arrays")
    return {"param_dtype": param_dtype, "param_bytes": param_bytes,
            "kv_dtype": kv_dtype, "kv_bytes": kv_bytes,
            "matmul_precision":
                jax.config.jax_default_matmul_precision or "default"}


def stop_server(srv):
    """The SIGTERM drain sequence, without the signal."""
    srv.shutdown(drain_seconds=5.0)
