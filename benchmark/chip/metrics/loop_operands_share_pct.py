"""Share of the worker loop's busy time spent preparing the decode
programs' operands."""
import window


def read(spec, ctx):
    return window.loop_share(ctx, ("operands",))
