"""Milliseconds a join spends in the block pool past the hashing."""
import loop_steps


def read(spec, ctx):
    return loop_steps.ms_mean(spec, ctx)
