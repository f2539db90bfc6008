"""Share of the roofline that the dots3-note decode programs reach, from the
trace and the program's own counters.  Returns None (the metric is left out)
where the program has no such counters, as a parent older than them has
not."""
import decode_counters
import opcount
import opcount_dots3
import trace_programs


def read(spec, ctx):
    seconds, steps = trace_programs.device_time(spec, ctx)
    means = decode_counters.window_means(ctx)
    window = decode_counters._delta(ctx, "mxtpu_decode_window_tokens")
    chosen = decode_counters._delta(ctx, "mxtpu_index_keys_selected")
    n = decode_counters.slot_steps(ctx)
    if not seconds or not steps or means is None or window is None \
            or chosen is None:
        return None
    cfg = ctx["config"]
    slots, touched, context = means
    selected = chosen / n / opcount_dots3.layer_kinds(cfg)[0]
    served = ctx["served"]
    flops, moved = opcount_dots3.decode_step(
        cfg, slots, touched, context, window / n, selected,
        served["param_bytes"], served["kv_bytes"])
    least, bound = opcount.least_seconds(flops, moved, ctx["peaks"])
    ctx.setdefault("notes", []).append(
        f"dots3_decode_roofline_pct: {steps:.0f} steps in {seconds:.4f} s of "
        f"device time, a step: {slots:.1f} live slots, {touched:.1f} experts "
        f"touched, {context:.0f} written positions a slot of which "
        f"{window / n:.0f} inside the window and {selected:.0f} chosen, "
        f"{moved / 1e9:.3f} GB, least {least * 1e3:.3f} ms, bound by {bound}")
    return 100.0 * least * steps / seconds
