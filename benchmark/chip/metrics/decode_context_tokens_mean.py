"""Written positions a live slot had behind it at a decode step, mean."""
import decode_counters


def read(spec, ctx):
    return decode_counters.context_tokens_mean(ctx)
