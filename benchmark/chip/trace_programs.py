"""Device time of named programs in a traced run's reduction, for the
roofline readers that a configuration brings."""


def device_time(spec, ctx):
    """``(device seconds, executions)`` of the trace's programs whose name
    ends in one of ``spec["programs"]`` — ``{suffix: steps an execution}``,
    a number or the name of a key of the configuration's ``deployment``."""
    dep = ctx["config"]["deployment"]
    seconds = steps = 0.0
    for prog, row in (ctx.get("trace") or {}).get("programs", {}).items():
        for pattern, per_exec in spec["programs"].items():
            if prog.endswith(pattern):
                n = dep[per_exec] if isinstance(per_exec, str) else per_exec
                seconds += row["seconds"]
                steps += row["count"] * n
    return seconds, steps
