"""Share of the worker loop's busy time spent in host work."""
import window


def read(spec, ctx):
    return window.loop_share(ctx, window.HOST_PHASES)
