"""Share of the roofline that the Qwen3-Next decode programs reach, from the
trace and the program's own counters.  Returns None (the metric is left out)
where the program has no such counters or the run has no trace."""
import decode_counters
import opcount
import opcount_qwen3next
import trace_programs


def read(spec, ctx):
    seconds, steps = trace_programs.device_time(spec, ctx)
    means = decode_counters.window_means(ctx)
    served = ctx.get("served") or {}
    if not seconds or not steps or means is None \
            or "state_bytes" not in served:
        return None
    slots, touched, context = means
    flops, moved = opcount_qwen3next.decode_step(
        ctx["config"], slots, touched, context, served["param_bytes"],
        served["kv_bytes"], served["state_bytes"])
    least, bound = opcount.least_seconds(flops, moved, ctx["peaks"])
    ctx.setdefault("notes", []).append(
        f"qwen3next_decode_roofline_pct: {steps:.0f} steps in "
        f"{seconds:.4f} s of device time, a step: {slots:.1f} live slots, "
        f"{touched:.1f} experts touched, {context:.0f} written positions a "
        f"slot, {moved / 1e9:.3f} GB, least {least * 1e3:.3f} ms, bound by "
        f"{bound}")
    return 100.0 * least * steps / seconds
