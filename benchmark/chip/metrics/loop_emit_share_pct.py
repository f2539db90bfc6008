"""Share of the worker loop's busy time spent in the batcher's emit and
admission."""
import window


def read(spec, ctx):
    return window.loop_share(ctx, ("emit", "admit"))
