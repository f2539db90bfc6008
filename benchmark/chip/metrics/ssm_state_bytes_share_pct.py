"""Share of a decode step's bytes that are the state rows' own: what the
one-token step of the state-space layers read and wrote, from the program's
counters (``mxtpu_ssm_step_rows_total`` x ``mxtpu_ssm_state_bytes`` x 2: a
row read once and written once), over the bytes `opcount_granite4.decode_step`
counts for the window's mean step.  Returns None where the program has no such
series (a program without state-space layers, or older than them)."""
import decode_window
import opcount_granite4
import window


def _gauge(ctx, name):
    model = ctx["config"]["deployment"]["model_name"]
    series = window._state(ctx, "snap1", "gauges", name) or {}
    for key, value in series.get("values", {}).items():
        if window._labels(key).get("model") == model:
            return value
    return None


def read(spec, ctx):
    rows = window.counter_by(ctx, "mxtpu_ssm_step_rows_total", "model")
    row_bytes = _gauge(ctx, "mxtpu_ssm_state_bytes")
    served = ctx.get("served") or {}
    if not rows or not row_bytes or "state_bytes" not in served \
            or "mamba_n_heads" not in ctx["config"]:
        return None
    step = decode_window.window_step(ctx)
    if step is None:
        return None
    steps, slots, context = step
    _, moved = opcount_granite4.decode_step(
        ctx["config"], slots, context, served["param_bytes"],
        served["kv_bytes"], served["state_bytes"])
    state = 2.0 * sum(rows.values()) * row_bytes
    ctx.setdefault("notes", []).append(
        f"ssm_state_bytes_share_pct: {sum(rows.values()):.0f} rows of "
        f"{row_bytes / 1e6:.1f} MB updated in {steps:.0f} steps: "
        f"{state / steps / 1e9:.3f} GB of a step's {moved / 1e9:.3f} GB")
    return 100.0 * state / (steps * moved)
