"""Window means of what the decode programs count about themselves
(`mxtpu_moe_experts_touched`, `mxtpu_decode_context_tokens`), for the metrics
that read them.  Everything returns None where the program has no such
counter (a program older than it) or the window holds nothing."""
import readers
import window


def decode_steps(ctx):
    """Decode steps of the measured window, from the dispatch ledger's window
    delta: a decode dispatch is one step, a burst ``scan_steps``."""
    dep = ctx["config"]["deployment"]
    r0, r1 = (readers._program_rows(ctx.get(k), dep["model_name"])
              for k in ("snap0", "snap1"))
    return sum(per * (r1.get(name, {}).get("dispatches", 0)
                      - r0.get(name, {}).get("dispatches", 0))
               for name, per in (("decode", 1),
                                 ("decode_burst", dep["scan_steps"])))


def slot_steps(ctx):
    """Tokens the decode and burst programs emitted in the window: one a live
    slot a step."""
    tokens = window.counter_by(ctx, "mxtpu_generate_tokens", "path")
    if not tokens:
        return None
    return tokens.get("step", 0.0) + tokens.get("burst", 0.0)


def _delta(ctx, name):
    by_model = window.counter_by(ctx, name, "model")
    if not by_model:
        return None
    return by_model.get(ctx["config"]["deployment"]["model_name"])


def context_tokens_mean(ctx):
    """Written positions a live slot had behind it at a step."""
    context, n = _delta(ctx, "mxtpu_decode_context_tokens"), slot_steps(ctx)
    return context / n if context is not None and n else None


def window_means(ctx):
    """``(live slots a step, experts touched a step, written positions a
    live slot)`` over the measured window."""
    steps, n = decode_steps(ctx), slot_steps(ctx)
    touched = _delta(ctx, "mxtpu_moe_experts_touched")
    context = context_tokens_mean(ctx)
    if not steps or not n or touched is None or context is None:
        return None
    return n / steps, touched / steps, context
