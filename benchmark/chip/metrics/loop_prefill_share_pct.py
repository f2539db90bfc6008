"""Share of the worker loop's busy time spent on prefills, host and wait."""
import window


def read(spec, ctx):
    return window.loop_share(ctx, ("prefill_host", "prefill_wait"))
