"""Experts held here that got at least one token, a layer-step."""
import decode_counters


def read(spec, ctx):
    means = decode_counters.window_means(ctx)
    cfg = ctx["config"]
    layers = cfg.get("num_hidden_layers", 0) - cfg.get("num_dense_layers", 0)
    if means is None or layers <= 0:
        return None
    return means[1] / layers
