"""Positions a windowed layer reads behind a live slot's decode step, mean:
min(written positions, window) slot by slot.  None where the program has no
such counter (a program older than it, a model without a window)."""
import decode_counters


def read(spec, ctx):
    window = decode_counters._delta(ctx, "mxtpu_decode_window_tokens")
    n = decode_counters.slot_steps(ctx)
    return window / n if window is not None and n else None
