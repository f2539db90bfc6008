"""Share of the roofline that the Granite 4.0-H decode programs reach, from the
trace and the program's own counters.  Returns None (the metric is left out)
where the program has no such counters or the run has no trace."""
import decode_window
import opcount
import opcount_granite4
import trace_programs


def read(spec, ctx):
    seconds, steps = trace_programs.device_time(spec, ctx)
    step = decode_window.window_step(ctx)
    served = ctx.get("served") or {}
    if not seconds or not steps or step is None \
            or "state_bytes" not in served \
            or "mamba_n_heads" not in ctx["config"]:
        return None
    _, slots, context = step
    flops, moved = opcount_granite4.decode_step(
        ctx["config"], slots, context, served["param_bytes"],
        served["kv_bytes"], served["state_bytes"])
    least, bound = opcount.least_seconds(flops, moved, ctx["peaks"])
    ctx.setdefault("notes", []).append(
        f"granite4_decode_roofline_pct: {steps:.0f} steps in "
        f"{seconds:.4f} s of device time, a step: {slots:.1f} live slots, "
        f"{context:.0f} written positions a slot, {moved / 1e9:.3f} GB, "
        f"least {least * 1e3:.3f} ms, bound by {bound}")
    return 100.0 * least * steps / seconds
