"""Share of the host phases' wall time the worker thread spent off the CPU."""
import loop_steps


def read(spec, ctx):
    return loop_steps.host_offcpu_pct(ctx)
