"""The window's mean decode step, for the metrics of a model whose layers
count nothing in the program (`decode_counters.window_means` also wants the
experts touched): steps from the dispatch ledger, live slots and context from
the host's counters.  None where the program has no such counter or the window
holds nothing."""
import decode_counters


def window_step(ctx):
    """``(decode steps, live slots a step, written positions a live slot)``
    of the measured window, or None."""
    steps, n = decode_counters.decode_steps(ctx), \
        decode_counters.slot_steps(ctx)
    context = decode_counters.context_tokens_mean(ctx)
    if not steps or not n or context is None:
        return None
    return steps, n / steps, context
