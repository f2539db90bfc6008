"""Share of the roofline that the Granite 4.0-H prefill programs reach: the
traced stretch's device time of the two prefill programs against the least
time of as many calls of the window's mean size.  Returns None where the
program has no such counters or the run has no trace."""
import opcount
import opcount_granite4
import readers
import trace_programs
import window


def read(spec, ctx):
    seconds, calls = trace_programs.device_time(spec, ctx)
    computed = window.counter_by(ctx, "mxtpu_prefill_tokens", "path")
    served = ctx.get("served") or {}
    dep = ctx["config"]["deployment"]
    r0, r1 = (readers._program_rows(ctx.get(k), dep["model_name"])
              for k in ("snap0", "snap1"))
    n = sum(r1.get(p, {}).get("dispatches", 0)
            - r0.get(p, {}).get("dispatches", 0)
            for p in ("prefill", "prefill_ext"))
    if not seconds or not calls or not computed or not n \
            or "state_bytes" not in served \
            or "mamba_n_heads" not in ctx["config"]:
        return None
    hit = window.counter_by(ctx, "mxtpu_prefix_hit_tokens", "model") or {}
    positions = sum(computed.values()) / n
    context = hit.get(dep["model_name"], 0.0) / n
    flops, moved = opcount_granite4.prefill_call(
        ctx["config"], positions, context, served["param_bytes"],
        served["kv_bytes"], served["state_bytes"])
    least, bound = opcount.least_seconds(flops, moved, ctx["peaks"])
    ctx.setdefault("notes", []).append(
        f"granite4_prefill_roofline_pct: {calls:.0f} calls in "
        f"{seconds:.4f} s of device time; the window's mean call: "
        f"{positions:.0f} positions after {context:.0f} cached, "
        f"{flops / 1e12:.3f} TFLOP, {moved / 1e9:.3f} GB, least "
        f"{least * 1e3:.3f} ms, bound by {bound}")
    return 100.0 * least * calls / seconds
