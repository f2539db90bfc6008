"""Share of the roofline that the SmallThinker decode programs reach, from
the trace and the program's own counters.  Returns None (the metric is left
out) where the program has no such counters, as a parent older than them has
not."""
import decode_counters
import opcount
import opcount_smallthinker

def read(spec, ctx):
    trace = ctx.get("trace") or {}
    cfg, dep = ctx["config"], ctx["config"]["deployment"]
    seconds = steps = 0.0
    for prog, row in trace.get("programs", {}).items():
        for pattern, per_exec in spec["programs"].items():
            if prog.endswith(pattern):
                n = dep[per_exec] if isinstance(per_exec, str) else per_exec
                seconds += row["seconds"]
                steps += row["count"] * n
    means = decode_counters.window_means(ctx)
    window = decode_counters._delta(ctx, "mxtpu_decode_window_tokens")
    n = decode_counters.slot_steps(ctx)
    if not seconds or not steps or means is None or window is None:
        return None
    slots, touched, context = means
    served = ctx["served"]
    flops, moved = opcount_smallthinker.decode_step(
        cfg, slots, touched, context, window / n, served["param_bytes"],
        served["kv_bytes"])
    least, bound = opcount.least_seconds(flops, moved, ctx["peaks"])
    ctx.setdefault("notes", []).append(
        f"smallthinker_decode_roofline_pct: {steps:.0f} steps in "
        f"{seconds:.4f} s of device time, a step: {slots:.1f} live slots, "
        f"{touched:.1f} experts touched, {context:.0f} written positions a "
        f"slot of which {window / n:.0f} inside the window, "
        f"{moved / 1e9:.3f} GB, least {least * 1e3:.3f} ms, bound by {bound}")
    return 100.0 * least * steps / seconds
