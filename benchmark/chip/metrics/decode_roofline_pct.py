"""Share of the roofline that the decode programs reach, from the trace."""
import opcount


def read(spec, ctx):
    trace = ctx.get("trace") or {}
    cfg, dep = ctx["config"], ctx["config"]["deployment"]
    model = dep["model_name"]
    seconds = steps = 0.0
    for prog, row in trace.get("programs", {}).items():
        for pattern, per_exec in spec["programs"].items():
            if prog.endswith(pattern):
                n = dep[per_exec] if isinstance(per_exec, str) else per_exec
                seconds += row["seconds"]
                steps += row["count"] * n
    live = [(s["models"][model]["slots_in_use"],
             s["models"][model]["kv_blocks_in_use"] * dep["block_size"])
            for s in ctx.get("samples", []) if model in s.get("models", {})]
    if not seconds or not steps or not live:
        return None
    slots = sum(a for a, _ in live) / len(live)
    kv_tokens = sum(b for _, b in live) / len(live)
    # widths as the child read them off the served arrays, not as stated
    served = ctx["served"]
    flops, moved = opcount.gpt_decode_step(
        cfg, slots, kv_tokens, served["param_bytes"], served["kv_bytes"])
    least, bound = opcount.least_seconds(flops, moved, ctx["peaks"])
    ctx.setdefault("notes", []).append(
        f"decode_roofline_pct: {steps:.0f} steps in {seconds:.4f} s of device "
        f"time, mean live slots {slots:.1f}, live KV {kv_tokens:.0f} tokens, "
        f"least {least * 1e3:.3f} ms a step, bound by {bound}")
    return 100.0 * least * steps / seconds
