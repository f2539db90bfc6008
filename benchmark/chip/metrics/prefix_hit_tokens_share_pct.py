"""Share of the window's prompt positions that cached blocks (and, for a
recurrent model, the snapshot they end at) served."""
import window


def read(spec, ctx):
    computed = window.counter_by(ctx, "mxtpu_prefill_tokens", "path")
    if not computed:
        return None
    hit = (window.counter_by(ctx, "mxtpu_prefix_hit_tokens", "model")
           or {}).get(ctx["config"]["deployment"]["model_name"], 0.0)
    total = hit + sum(computed.values())
    return 100.0 * hit / total if total > 0 else None
