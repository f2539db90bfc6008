"""Share of the peak FLOP/s that one training step program reaches, from the
trace."""
import opcount


def read(spec, ctx):
    trace = ctx.get("trace") or {}
    cfg, job = ctx["config"], ctx["config"]["job"]
    seconds = count = 0.0
    for prog, row in trace.get("programs", {}).items():
        if any(prog.endswith(p) for p in spec["programs"]):
            seconds += row["seconds"]
            count += row["count"]
    if not seconds or not count:
        return None
    flops = opcount.bert_train_step(cfg, job["batch_size"], job["seq_len"])
    least, bound = opcount.least_seconds(flops, 0.0, ctx["peaks"])
    ctx.setdefault("notes", []).append(
        f"train_step_roofline_pct: {count:.0f} steps in {seconds:.4f} s of "
        f"device time, {flops / 1e12:.3f} TFLOP a step, bound by {bound}")
    return 100.0 * least * count / seconds
