"""Milliseconds an emit spends fanning tokens out to the streams."""
import loop_steps


def read(spec, ctx):
    return loop_steps.ms_mean(spec, ctx)
