"""Milliseconds a join spends hashing its prompt's blocks."""
import loop_steps


def read(spec, ctx):
    return loop_steps.ms_mean(spec, ctx)
