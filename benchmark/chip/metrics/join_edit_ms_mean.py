"""Milliseconds a join spends on its slot's row before the prefill."""
import loop_steps


def read(spec, ctx):
    return loop_steps.ms_mean(spec, ctx)
