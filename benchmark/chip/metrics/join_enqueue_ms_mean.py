"""Milliseconds a join spends enqueueing its prefill program."""
import loop_steps


def read(spec, ctx):
    return loop_steps.ms_mean(spec, ctx)
