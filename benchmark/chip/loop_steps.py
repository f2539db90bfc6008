"""Window means of the serving worker loop's step clock
(`mxtpu_serve_loop_step_seconds{phase,step}`, and the thread's CPU time
`mxtpu_serve_loop_cpu_seconds{phase}` beside `mxtpu_serve_loop_seconds`), for
the metrics that read them: milliseconds of a step a join or a dispatch, where
a join is a `prefill` or `prefill_ext` dispatch of the ledger and a dispatch a
`decode` or `decode_burst` one.  Everything returns None where the program has
no such counter (a program older than it) or the window holds no such call."""
import readers
import window

JOINS = ("prefill", "prefill_ext")
DISPATCHES = ("decode", "decode_burst")


def step_deltas(ctx):
    """``{(phase, step): seconds over the window}`` of the served model;
    None on a program without the counter."""
    name = "mxtpu_serve_loop_step_seconds"
    after = window._state(ctx, "snap1", "counters", name)
    if after is None:
        return None
    before = (window._state(ctx, "snap0", "counters", name)
              or {}).get("values", {})
    out = {}
    for key, value in after.get("values", {}).items():
        labels = window._labels(key)
        if labels.get("model") == window._model(ctx):
            out[labels.get("phase"), labels.get("step")] = \
                value - before.get(key, 0.0)
    return out


def step_seconds(ctx, steps, phases=None):
    """The window's seconds inside ``steps``, in ``phases`` (None: wherever
    they ran); None on a program without the counter."""
    deltas = step_deltas(ctx)
    if deltas is None:
        return None
    return sum(v for (phase, step), v in deltas.items()
               if step in steps and (phases is None or phase in phases))


def calls(ctx, programs):
    """Dispatches of ``programs`` in the window, from the dispatch ledger."""
    model = window._model(ctx)
    r0, r1 = (readers._program_rows(ctx.get(k), model)
              for k in ("snap0", "snap1"))
    return sum(r1.get(p, {}).get("dispatches", 0)
               - r0.get(p, {}).get("dispatches", 0) for p in programs)


def ms_mean(spec, ctx):
    """The metric files' one reduction: ``spec["steps"]`` in
    ``spec["phases"]`` (absent: all), in milliseconds a call of
    ``spec["per"]`` (``"joins"``, ``"dispatches"`` or both)."""
    seconds = step_seconds(ctx, spec["steps"], spec.get("phases"))
    n = calls(ctx, [p for kind in spec["per"]
                    for p in {"joins": JOINS, "dispatches": DISPATCHES}[kind]])
    if seconds is None or n <= 0:
        return None
    return 1e3 * seconds / n


def host_offcpu_pct(ctx):
    """Share (%) of the host phases' wall time in which the worker thread
    was not running on a CPU; the window's whole step table goes to the
    run's notes with it."""
    wall = window.counter_by(ctx, "mxtpu_serve_loop_seconds", "phase")
    cpu = window.counter_by(ctx, "mxtpu_serve_loop_cpu_seconds", "phase")
    if not wall or not cpu:
        return None
    wall_s = sum(wall.get(p, 0.0) for p in window.HOST_PHASES)
    if wall_s <= 0:
        return None
    cpu_s = sum(cpu.get(p, 0.0) for p in window.HOST_PHASES)
    ctx.setdefault("notes", []).extend(_table(ctx, wall, cpu))
    return 100.0 * (1.0 - cpu_s / wall_s)


def _table(ctx, wall, cpu):
    """One line a host phase: its wall and CPU seconds over the window, its
    steps in milliseconds, and the remainder no step covers."""
    deltas = step_deltas(ctx) or {}
    lines = [f"loop steps: {calls(ctx, JOINS)} joins, "
             f"{calls(ctx, DISPATCHES)} dispatches in the window"]
    for phase in window.HOST_PHASES:
        steps = {s: v for (p, s), v in sorted(deltas.items())
                 if p == phase and v}
        total = wall.get(phase, 0.0)
        rest = total - sum(steps.values())
        lines.append(
            f"loop steps: {phase} {total:.4f} s wall, "
            f"{cpu.get(phase, 0.0):.4f} s cpu = "
            + " + ".join(f"{k} {1e3 * v:.1f}" for k, v in steps.items())
            + f" + remainder {1e3 * rest:.1f} ms "
            f"({100.0 * rest / total if total > 0 else 0.0:.1f}%)")
    return lines
