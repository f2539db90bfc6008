"""Milliseconds a decode or burst dispatch spends in its enqueue."""
import loop_steps


def read(spec, ctx):
    return loop_steps.ms_mean(spec, ctx)
